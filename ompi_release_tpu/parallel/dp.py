"""Data parallelism: bucketed gradient allreduce.

The Horovod-style pattern the reference's ring allreduce serves
(``ompi/mca/coll/tuned/coll_tuned_allreduce.c:361``): every dp replica
holds a full gradient pytree; replicas psum (or mean) them. Bucketing
mirrors the reference's segmentation decision rules
(``coll_tuned_decision_fixed.c:70-80``) — small leaves are fused into
one flat collective so per-collective latency is amortized, exactly why
tuned switches algorithms by message size. Under XLA one psum per
bucket compiles to one fused ICI collective.

The fusion decision itself (greedy in-order same-dtype packing up to a
byte capacity) is :func:`coll.fusion.plan_buckets` — ONE definition
shared with the host-driver fusion buffer (``comm.fusion_buffer()``),
so the SPMD gradient path and the driver path coalesce identically.

Two execution modes:

:func:`allreduce_gradients`
    SPMD, inside ``shard_map``: one ``lax.psum`` per bucket (XLA
    pipelines the compiled collectives itself).

:class:`GradientSync`
    HOST-DRIVER, the async-progress-engine payoff: one
    ``comm.iallreduce`` per bucket issued up front, caller compute
    overlaps the wire traffic, ``wait()`` lands at the step boundary.
    The bucket plan is built ONCE per (tree structure, shapes, dtypes,
    bucket size) and cached — the persistent-collective shape: plan
    once, fire per step. With the ``progress_thread`` cvar on, the
    engine runs the bucket schedules off the caller (true overlap,
    measured by ``nbc_hidden_seconds``); in polling mode the buckets
    drain at ``wait()``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..mca import var as mca_var
from . import tree as _tree_mod


def register_vars() -> None:
    mca_var.register(
        "dp_bucket_bytes", "int", 4 * 1024 * 1024,
        "Gradient-allreduce bucket size in bytes (small leaves are "
        "flattened+concatenated up to this size per collective)",
    )


def allreduce_gradients(grads: Any, axis_name: str, *, mean: bool = True,
                        bucket_bytes: Optional[int] = None) -> Any:
    """Allreduce a gradient pytree over the dp axis.

    Leaves smaller than ``bucket_bytes`` (default: the dp_bucket_bytes
    config variable / tree_buckets tuned rules) are packed into flat
    buckets so each bucket is ONE psum; large leaves go through psum
    individually (XLA already tiles/pipelines a single large
    collective well). The planned pass itself is
    :func:`parallel.tree.tree_allreduce` — one planner, one plan
    cache, one packing layout for every tree-shaped collective.
    """
    # bucket_bytes=None resolves inside the tree pass through the
    # shared precedence (tree_buckets tuned rules > tree_bucket_bytes
    # > dp_bucket_bytes) — resolving here would bypass the rules
    return _tree_mod.tree_allreduce(grads, axis_name, mean=mean,
                                    bucket_bytes=bucket_bytes)


class GradientSync(_tree_mod.TreeSync):
    """Overlapped gradient-bucket allreduce for the host-driver path —
    the ALLREDUCE specialization of :class:`parallel.tree.TreeSync`
    (which also drives whole-tree reduce-scatter and allgather).

    Buffers follow the communicator's driver convention (leading axis
    = this process's member slices). Usage per step::

        pending = sync.issue(grads)   # one iallreduce per bucket
        ... compute (fwd/bwd of the next microbatch, optimizer prep)
        new_grads = pending.wait()    # step boundary

    Bitwise parity with the blocking path is structural: each bucket
    runs the identical allreduce the blocking call would, via the
    progress engine.
    """

    def __init__(self, comm, *, mean: bool = True,
                 bucket_bytes: Optional[int] = None) -> None:
        # bucket_bytes=None resolves per issue() through the shared
        # precedence (tree_buckets rules > tree_bucket_bytes >
        # dp_bucket_bytes), so runtime cvar tuning still applies
        super().__init__(comm, mean=mean, bucket_bytes=bucket_bytes)


def replicate_check(x: jax.Array, axis_name: str) -> jax.Array:
    """Debug guard: max |x - bcast(x from rank0)| across the dp axis —
    the memchecker-style replica-divergence detector (SURVEY §5 race
    detection); 0 when replicas agree."""
    rank = lax.axis_index(axis_name)
    root = lax.psum(jnp.where(rank == 0, x, jnp.zeros_like(x)), axis_name)
    return lax.pmax(jnp.max(jnp.abs(x - root)), axis_name)
