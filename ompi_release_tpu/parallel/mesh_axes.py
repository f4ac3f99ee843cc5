"""Canonical mesh-axis conventions for the parallelism strategies.

The reference maps ranks onto nodes/slots via rmaps (SURVEY §2.2); here
the mapping is a named multi-axis ``jax.sharding.Mesh`` over the ICI
torus. Axis order is chosen so the most bandwidth-hungry axis (tp) is
innermost — contiguous device ranges share ICI links, so tp collectives
ride the shortest paths, then sp/cp, then pp, then dp outermost (dp
gradients tolerate the longest routes / DCN).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

AXIS_DP = "dp"  # data parallel (gradient psum)
AXIS_PP = "pp"  # pipeline stages (ppermute ring)
AXIS_SP = "sp"  # sequence/context parallel (alltoall / K-V ring)
AXIS_EP = "ep"  # expert parallel (token-routing all-to-all)
AXIS_TP = "tp"  # tensor parallel (psum/all_gather, innermost)

#: outermost -> innermost
CANONICAL_ORDER = (AXIS_DP, AXIS_PP, AXIS_SP, AXIS_EP, AXIS_TP)


def build_parallel_mesh(
    dp: int = 1, pp: int = 1, sp: int = 1, ep: int = 1, tp: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Mesh with all five canonical axes (size-1 axes kept so PartitionSpecs
    are uniform regardless of which strategies are active)."""
    if devices is None:
        devices = jax.devices()
    shape = (dp, pp, sp, ep, tp)
    n = int(np.prod(shape))
    if n != len(devices):
        raise ValueError(
            f"dp*pp*sp*ep*tp = {n} but {len(devices)} devices available"
        )
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(arr, CANONICAL_ORDER)


def vary_like(x, ref):
    """Mark ``x`` varying over every manual axis ``ref`` varies over.

    shard_map's replication tracking (vma) types freshly-created
    constants as replicated; scan carries that will be overwritten with
    communicated data need their initial value cast to the same
    varying type or the carry types mismatch.
    """
    import jax as _jax
    from jax import lax as _lax

    want = getattr(_jax.typeof(ref), "vma", frozenset())
    have = getattr(_jax.typeof(x), "vma", frozenset())
    missing = tuple(sorted(want - have))
    return _lax.pcast(x, missing, to="varying") if missing else x


def vary_over(x, axes):
    """Mark ``x`` varying over the named manual axes (no-op for axes it
    already varies over, or outside shard_map)."""
    import jax as _jax
    from jax import lax as _lax

    have = getattr(_jax.typeof(x), "vma", frozenset())
    missing = tuple(a for a in axes if a not in have)
    return _lax.pcast(x, missing, to="varying") if missing else x


def axis_size_or_1(axis_name: str) -> int:
    """Axis size under trace; 1 when the axis is not in scope (so layer
    code can be written once and run with any subset of axes bound)."""
    from jax import lax

    try:
        return lax.psum(1, axis_name)
    except NameError:
        return 1
