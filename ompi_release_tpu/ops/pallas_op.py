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
iteration. bench.py uses these kernels for exactly that reason; the op
framework exposes them for large contiguous f32/bf16 reductions.

Block shapes: the axpy (read acc, read a, write acc -> 3 streams) uses
(256, 2048) f32 blocks, the 2-stream copy/scale kernel short, wide
ones. They were picked on a v5e under an earlier jax/libtpu; their
speed under the installed one is not measured (ROADMAP S1). What is
established is that they compile: three double-buffered (256, 2048)
f32 streams are 12 MiB of VMEM, inside v5e's 16 MiB default scoped
limit.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..mca import component as mca_component

#: measured-optimal f32 block shapes (rows, cols)
AXPY_BLOCK: Tuple[int, int] = (256, 2048)
SCALE_BLOCK: Tuple[int, int] = (128, 2048)
#: second copy-ceiling candidate (also ~820-840 GB/s measured); the
#: bench measures both and takes the per-round max as the ceiling
SCALE_BLOCK_ALT: Tuple[int, int] = (32, 8192)
#: third candidate: a 2026-07 re-sweep measured the shortest/widest
#: block winning the copy kernel under that session's conditions
#: (679 vs 657/653 GB/s for the other two) — candidates exist so the
#: ceiling is the best the chip demonstrably does TODAY, whichever
#: shape that takes
SCALE_BLOCK_ALT2: Tuple[int, int] = (16, 16384)


def _interpret() -> bool:
    # CPU (tests, simulator mesh) runs the same kernels interpreted
    return jax.default_backend() != "tpu"


def _blocked_call(kernel, nin: int, rows: int, cols: int, blk_rows: int,
                  dtype, vma=frozenset()):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if rows % blk_rows:
        # a truncated grid would silently skip the tail — fatal in a
        # bandwidth benchmark (unprocessed rows inflate the number)
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


def make_axpy_loop(rows: int, cols: int, c: float = 0.999,
                   blk_rows: int = None, dtype=jnp.float32):
    """K-iteration benchmark loop over the axpy kernel (bench.py's
    measurement body: per-iteration traffic = 3 x rows x cols x
    itemsize). ``blk_rows`` overrides the tuned block height for
    small-message sweep points whose whole array is below one block."""
    if blk_rows is None:
        blk_rows = min(AXPY_BLOCK[0], rows)

    def kernel(a_ref, acc_ref, out_ref):
        out_ref[:] = acc_ref[:] * c + a_ref[:]

    call = _blocked_call(kernel, 2, rows, cols, blk_rows, dtype)

    @partial(jax.jit, static_argnums=1)
    def loop(a, k):
        def body(i, acc):
            return call(a, acc)

        acc = jax.lax.fori_loop(
            0, k, body, jnp.zeros((rows, cols), dtype)
        )
        return acc[0, 0] + acc[-1, -1]  # 8-byte completion checksum

    return loop


def make_scale_loop(rows: int, cols: int, c: float = 1.0001,
                    blk_rows: int = None, dtype=jnp.float32):
    """K-iteration loop over the 2-stream scale kernel (the measured
    HBM copy ceiling: read + write per iteration)."""
    if blk_rows is None:
        blk_rows = min(SCALE_BLOCK[0], rows)

    def kernel(x_ref, out_ref):
        out_ref[:] = x_ref[:] * c

    call = _blocked_call(kernel, 1, rows, cols, blk_rows, dtype)

    @partial(jax.jit, static_argnums=1)
    def loop(a, k):
        def body(i, acc):
            return call(acc)

        acc = jax.lax.fori_loop(0, k, body, a)
        return acc[0, 0] + acc[-1, -1]

    return loop


def make_transpose_loop(n: int, block: int = 256, dtype=jnp.int32):
    """K-iteration loop over a blocked (n, n) transpose — the
    single-chip analogue of the 2-D-torus MPI_Alltoall shuffle
    (BASELINE config 5): every (i, j) block moves to (j, i), all-pairs
    data movement through HBM.

    The loop body applies the transpose TWICE, 4 streams (2 reads + 2
    writes of the full array) per iteration, and callers must count
    ``4 * n * n * itemsize`` bytes.  Why: a ``fori_loop`` carry lives
    in a FIXED buffer across iterations (XLA while-loop buffer
    assignment), so a single non-aliased kernel per iteration forces
    XLA to copy its fresh output back into the carry buffer — 2N
    uncounted extra bytes that halved the reported bandwidth for three
    rounds (the r03 "alltoall at 0.49 of ceiling" gap was exactly
    this, probes 5-7: square blocks, run length, 1-D vs 2-D grids all
    measured identical; only aliasing moved the number).  With two
    calls per body, call #1's input buffer is dead when call #2 runs,
    XLA reuses it for #2's output, the carry address is stable and no
    copy is inserted — measured at copy-ceiling parity.  A same-buffer
    blocked transpose cannot use ``input_output_aliases`` directly
    (block (j, i) would be clobbered before grid step (j, i) reads
    it), which is why the scale/axpy kernels alias and this one
    double-applies instead.  XLA cannot fold T(T(x)) = x across the
    two calls: a pallas_call is opaque."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if n % block:
        raise ValueError(f"n ({n}) must be a multiple of block ({block})")

    def kernel(x_ref, out_ref):
        out_ref[:] = x_ref[:].T

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), dtype),
        grid=(n // block, n // block),
        in_specs=[pl.BlockSpec((block, block), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (j, i),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )

    @partial(jax.jit, static_argnums=1)
    def loop(a, k):
        def body(i, acc):
            return call(call(acc))

        acc = jax.lax.fori_loop(0, k, body, a)
        return acc[0, 0] + acc[-1, -1]

    return loop, call


def make_chain_loop(hops: int = 4, dtype=jnp.float32):
    """K-iteration loop over ``hops`` serially-dependent tiny (8, 128)
    kernels — the single-chip analogue of examples/ring_c.c's 4-rank
    token ring (each hop = one kernel dispatch, data-dependent on the
    previous). Slope / hops = per-hop launch+HBM-roundtrip latency."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    spec = pl.BlockSpec((8, 128), lambda: (0, 0),
                        memory_space=pltpu.VMEM)

    def kernel(x_ref, out_ref):
        out_ref[:] = x_ref[:] + 1

    call = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), dtype),
        in_specs=[spec], out_specs=spec, interpret=_interpret(),
    )

    @partial(jax.jit, static_argnums=1)
    def loop(a, k):
        def body(i, acc):
            for _ in range(hops):
                acc = call(acc)
            return acc

        acc = jax.lax.fori_loop(0, k, body, a)
        return acc[0, 0] + acc[-1, -1]

    return loop
