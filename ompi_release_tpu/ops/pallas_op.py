"""Pallas streaming reduction kernels — the accelerated op component.

The reference's reduction hot loop is a C elementwise loop per
(op x dtype) (``ompi/mca/op/base/op_base_functions.c``); its ``op`` MCA
framework exists so accelerated components can override those kernels
(``ompi/mca/op``). This is that component for TPU: hand-tiled Pallas
kernels for the HBM-bound streaming shapes where explicit VMEM blocking
reaches the memory ceiling.

Why Pallas here at all (SURVEY §7 step 5, "where XLA's built-ins
lose"): XLA is free to algebraically fold repeated affine updates
across loop iterations (acc*c+a twice = acc*c^2 + (ac+a)), which
silently turns a bandwidth loop into a flops one. A ``pallas_call`` is
opaque to XLA, so a timing loop over it moves real HBM traffic every
iteration. The op framework exposes these kernels for large contiguous
f32/bf16 reductions.

Block shapes: the axpy (read acc, read a, write acc -> 3 streams) uses
(256, 2048) f32 blocks, the 2-stream copy/scale kernel short, wide
ones. They were picked on a v5e under an earlier jax/libtpu; their
speed under the installed one is not measured (no cell of the
benchmark runs them). What is
established is that they compile: three double-buffered (256, 2048)
f32 streams are 12 MiB of VMEM, inside v5e's 16 MiB default scoped
limit.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..mca import component as mca_component

#: measured-optimal f32 block shapes (rows, cols)
AXPY_BLOCK: Tuple[int, int] = (256, 2048)
SCALE_BLOCK: Tuple[int, int] = (128, 2048)


def _interpret() -> bool:
    # CPU (tests, simulator mesh) runs the same kernels interpreted
    return jax.default_backend() != "tpu"


def _blocked_call(kernel, nin: int, rows: int, cols: int, blk_rows: int,
                  dtype, vma=frozenset()):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if rows % blk_rows:
        # a truncated grid would silently skip the tail
        raise ValueError(
            f"rows ({rows}) must be a multiple of the block height "
            f"({blk_rows})"
        )
    spec = pl.BlockSpec((blk_rows, cols), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        # vma: inside shard_map the output varies across the mesh axes
        # its inputs vary over — propagated from the caller's tracers
        # (replication typing would otherwise reject the call)
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype, vma=vma),
        grid=(rows // blk_rows,),
        in_specs=[spec] * nin,
        out_specs=spec,
        input_output_aliases={nin - 1: 0},
        interpret=_interpret(),
    )


def axpy(a: jax.Array, acc: jax.Array, c: float = 1.0) -> jax.Array:
    """acc*c + a as a tiled streaming kernel (the SUM/AXPY hot loop).

    Arrays must be equal-shape f32/bf16; arbitrary shapes are flattened
    and padded up to a whole number of blocks internally.
    """
    def kernel(a_ref, acc_ref, out_ref):
        out_ref[:] = acc_ref[:] * c + a_ref[:]

    return _apply_blocked(kernel, 2, AXPY_BLOCK, a, acc)


def scale(x: jax.Array, c: float) -> jax.Array:
    """x*c streaming (2-stream read+write: the copy-ceiling kernel)."""
    def kernel(x_ref, out_ref):
        out_ref[:] = x_ref[:] * c

    return _apply_blocked(kernel, 1, SCALE_BLOCK, x)


def _apply_blocked(kernel, nin: int, block: Tuple[int, int], *arrays):
    blk_rows, cols = block
    x0 = arrays[0]
    shape, dtype = x0.shape, x0.dtype
    n = x0.size
    rows = -(-n // cols)
    # never pad a short input up to the full tuned block height — cap
    # the block at the data, but not below Mosaic's minimum sublane
    # tile (8 for 4-byte types, 16 for bf16's packed (16, 128) tile)
    min_rows = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    blk_rows = max(min_rows, min(blk_rows, rows))
    rows = -(-rows // blk_rows) * blk_rows  # whole blocks
    padded_n = rows * cols

    def prep(a):
        flat = a.reshape(-1)
        if padded_n != n:
            from ..parallel.mesh_axes import vary_like

            # pad zeros must carry the data's varying-axis type or the
            # concat (and the kernel) fail shard_map's vma check
            flat = jnp.concatenate(
                [flat, vary_like(jnp.zeros((padded_n - n,), dtype),
                                 flat)]
            )
        return flat.reshape(rows, cols)

    prepped = [prep(a) for a in arrays]
    vma = frozenset()
    for p in prepped:  # union: any varying input makes the out vary
        vma = vma | getattr(jax.typeof(p), "vma", frozenset())
    call = _blocked_call(kernel, nin, rows, cols, blk_rows, dtype,
                         vma=vma)
    out = call(*prepped)
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# op-framework component: the accelerated override the framework exists
# for (``ompi/mca/op`` — accelerated components outrank the base C
# loops and claim the shapes they beat them on)
# ---------------------------------------------------------------------------

def _pallas_sum_fn(a, b):
    """a + b as the tiled 3-stream streaming kernel: explicit VMEM
    blocking at the measured-optimal axpy block shape. Equal shapes
    only — exactly what collective local-reduction steps pass. No
    scalar constant in the kernel body (a literal's empty varying-axis
    type would clash with ref reads under shard_map's vma tracking)."""
    def kernel(a_ref, b_ref, out_ref):
        out_ref[:] = b_ref[:] + a_ref[:]

    return _apply_blocked(kernel, 2, AXPY_BLOCK, a, b)


_pallas_sum_op = None


def make_pallas_sum():
    # ONE Op instance for the component's lifetime: program caches key
    # compiled collectives by the op OBJECT, so a fresh Op per lookup
    # would recompile on every resolved call
    global _pallas_sum_op
    if _pallas_sum_op is None:
        from .op import Op

        _pallas_sum_op = Op("sum[pallas]", _pallas_sum_fn,
                            commutative=True, identity=lambda d: 0,
                            lax_collective=None)
    return _pallas_sum_op


class PallasOpComponent(mca_component.Component):
    """Claims large contiguous f32/bf16 SUM reductions; everything else
    falls through to the xla component. The threshold is the measured
    crossover where explicit blocking stops being noise against the
    compiler's fusion (small arrays are latency-bound; the kernel's
    padding to whole blocks would dominate)."""

    NAME = "pallas"
    PRIORITY = 20  # outranks xla (10): queried first, claims narrowly

    def register_vars(self) -> None:
        from ..mca import var as mca_var

        mca_var.register(
            "op_pallas_threshold", "size", 4 * 1024 * 1024,
            "Minimum reduction size in bytes for the pallas streaming "
            "SUM kernel to claim the op (below it, XLA fusion wins)",
        )

    def lookup(self, name: str, dtype=None, nbytes: int = 0):
        from ..mca import var as mca_var

        if name != "sum" or dtype is None:
            return None
        if str(jnp.dtype(dtype)) not in ("float32", "bfloat16"):
            return None
        if nbytes < int(mca_var.get("op_pallas_threshold",
                                    4 * 1024 * 1024)):
            return None
        return make_pallas_sum()
