"""BASELINE bench suite: all 5 configs, one JSON line each.

BASELINE.json's five configs, each emitting one JSON metric line, the
headline (op_sum_256MiB_f32_hbm_bw, comparable across rounds) LAST:

  1. ring        — examples/ring_c.c 4-rank token ring
  2. allreduce   — OSU-style f32 SUM sweep, 8 B..256 MiB
  3. bcast       — contiguous f32 (+ allgather bf16, config 3's pair)
  4. reduce_scatter_block — f32 SUM (ZeRO-style 64 MiB gradient shard)
  5. alltoall    — int32 all-pairs shuffle (2-D torus)

With n >= 2 devices the configs run the framework's own SPMD
collectives (coll/spmd.py kernels under shard_map). On ONE chip — the
driver's bench environment — each config runs its single-chip
op-kernel analogue from ompi_release_tpu/ops/pallas_op.py: the
HBM-bound data movement the collective would perform locally
(allreduce/reduce_scatter -> the 3-stream SUM/axpy hot loop,
bcast/allgather -> the 2-stream copy, alltoall -> the blocked
transpose shuffle, ring -> chained dependent kernel dispatches).
Pallas kernels on purpose: a pallas_call is opaque to XLA, so the
timing loop cannot be algebraically folded across iterations.

Timing: each measurement jits a fori_loop of K iterations and takes the
(K_hi - K_lo) slope, so fixed per-call host latency cancels. Completion
is forced by fetching an 8-byte checksum.

The ceiling (the "baseline" in vs_baseline) is self-referential, which
ROADMAP S1b replaces with published peaks: (a) every round interleaves
ALL loops, metric and ceiling alike; (b) the ceiling is the per-round
MAX bandwidth any 2-stream copy candidate OR the metric itself
achieved, so vs_baseline <= 1.0 by construction; (c) each line carries
the ceiling and its cross-round coefficient of variation; (d) sweep
points whose working set fits in on-chip memory report tier "on-chip"
with vs_baseline null rather than a fake HBM ratio.

A run that finds no accelerator fails: only an explicit
JAX_PLATFORMS=cpu run is a CPU run, and every line of one carries
tier_label "loopback-cpu". A phase that raises ends the run non-zero.

Prints one JSON object per line; the LAST line is the headline
{"metric", "value", "unit", "vs_baseline", ...} the driver parses.
"""

import json
import sys
import time
from functools import partial

import numpy as np

MiB = 1024 * 1024
SWEEP_BYTES = [8, 64 * 1024, MiB, 16 * MiB, 256 * MiB]
# largest working set eligible for the "on-chip" tier label (v5e VMEM
# is 128 MiB; leave headroom for double-buffering scratch)
ONCHIP_WS = 112 * MiB


def _human(nbytes):
    for unit, div in (("MiB", 1024 * 1024), ("KiB", 1024)):
        if nbytes >= div:
            return f"{nbytes // div}{unit}"
    return f"{nbytes}B"


def _sync(r):
    np.asarray(r)  # tiny checksum fetch forces remote completion


def _timed(fn, args, k):
    t0 = time.perf_counter()
    _sync(fn(*args, k))
    return time.perf_counter() - t0


def _ks(traffic_bytes_per_iter, on_tpu):
    """Static initial (K_lo, K_hi) guess from HBM traffic at
    ~700 GB/s with a 3 us dispatch floor. Only a STARTING POINT:
    sub-VMEM working sets run 5-20x faster than the HBM estimate
    (on-chip residency), so the real K is set by :func:`_calibrate_k`
    from a measured per-iteration time."""
    if not on_tpu:
        return (2, 18)
    est = max(traffic_bytes_per_iter / 700e9, 3e-6)
    k_hi = max(258, int(0.75 / est))
    return (max(2, k_hi // 32), k_hi)


K_CAP = 4_000_000
TARGET_S = 0.75


def _calibrate_k(loop, args, static_hi):
    """Measure the loop's actual per-iteration time and size K_hi for
    ~TARGET_S seconds of device time: the probe grows K geometrically
    until the K-call exceeds the base call by >250 ms, so the final
    K_hi-K_lo delta towers over per-call host jitter. A K sized from
    the HBM estimate alone leaves VMEM-resident loops with deltas
    inside that jitter."""
    # min-of-N: host latency spikes only ADD time, so minima approach
    # the true floor
    base = min(_timed(loop, args, 2) for _ in range(3))
    k = max(64, static_hi // 8)
    while True:
        dt = min(_timed(loop, args, k) for _ in range(2)) - base
        if dt > 0.25 or k >= K_CAP:
            per = max(dt / k, 2e-8)
            break
        k *= 4
    k_hi = min(max(int(TARGET_S / per), 258), K_CAP)
    return max(2, k_hi // 32), k_hi


def _run_rounds(specs, rounds):
    """Interleaved slope timing: every round times every loop's K_lo
    and K_hi back to back, so cross-loop ratios (metric/ceiling) are
    taken between samples milliseconds apart, not minutes."""
    for s in specs:  # compile + warm both K values
        _sync(s["loop"](*s["args"], s["k_lo"]))
        _sync(s["loop"](*s["args"], s["k_hi"]))
    slopes = [[] for _ in specs]
    lo_t = [[] for _ in specs]
    hi_t = [[] for _ in specs]
    for r in range(rounds):
        for i, s in enumerate(specs):
            tlo = _timed(s["loop"], s["args"], s["k_lo"])
            thi = _timed(s["loop"], s["args"], s["k_hi"])
            lo_t[i].append(tlo)
            hi_t[i].append(thi)
            slopes[i].append(
                max((thi - tlo) / (s["k_hi"] - s["k_lo"]), 1e-12)
            )
    _flag_unstable(specs, lo_t, hi_t)
    return np.asarray(slopes)  # (n_specs, rounds)


def _flag_unstable(specs, lo_t, hi_t):
    for i, s in enumerate(specs):
        # a median K-delta inside per-call host jitter means the
        # slope is noise, not signal — flag rather than report garbage
        s["unstable"] = (
            np.median(hi_t[i]) - np.median(lo_t[i])
        ) < 0.05 and jnp_on_tpu()


def jnp_on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def _sweep_geom(elems):
    """(rows, cols, blk_rows) for an axpy sweep point: full tuned
    blocks for large sizes, one minimal (8, 128)-multiple tile padded
    up for tiny ones."""
    cols = 2048 if elems >= 8 * 2048 else 128
    rows = max(8, -(-elems // cols))
    blk = min(256, -(-rows // 8) * 8)
    rows = -(-rows // blk) * blk
    return rows, cols, blk


def _single_chip_specs(jax, jnp, dev, on_tpu):
    """The 5 configs as single-chip op-kernel analogues + ceiling
    candidates. Returns (specs, ceiling_names)."""
    from ompi_release_tpu.ops import pallas_op

    put = lambda a: jax.device_put(a, dev)
    specs = []

    # config 1: ring — 4 chained dependent kernel dispatches per iter
    ring_loop = pallas_op.make_chain_loop(hops=4)
    k_lo, k_hi = _ks(0, on_tpu)  # dispatch-latency bound
    specs.append(dict(
        name="ring_4hop", loop=ring_loop,
        args=(put(jnp.zeros((8, 128), jnp.float32)),),
        k_lo=k_lo, k_hi=k_hi, nbytes=None, hops=4,
    ))

    # config 2: allreduce sweep — the SUM op hot loop (3 HBM streams)
    sweep = SWEEP_BYTES if on_tpu else SWEEP_BYTES[:3]
    for size in sweep:
        elems = max(1, size // 4)
        rows, cols, blk = _sweep_geom(elems)
        loop = pallas_op.make_axpy_loop(rows, cols, blk_rows=blk)
        k_lo, k_hi = _ks(3 * size, on_tpu)
        specs.append(dict(
            name=f"allreduce_{_human(size)}", loop=loop,
            args=(put(jnp.ones((rows, cols), jnp.float32)),),
            k_lo=k_lo, k_hi=k_hi, nbytes=3 * size, size=size,
            ws=2 * size,
        ))

    big = 256 * MiB if on_tpu else 4 * MiB

    # config 3: bcast f32 + allgather bf16 — 2-stream copy traffic
    for nm, dtype, isz in (("bcast_f32", jnp.float32, 4),
                           ("allgather_bf16", jnp.bfloat16, 2)):
        elems = big // isz
        cols = 2048
        rows = elems // cols
        loop = pallas_op.make_scale_loop(rows, cols, dtype=dtype)
        k_lo, k_hi = _ks(2 * big, on_tpu)
        specs.append(dict(
            name=nm, loop=loop, args=(put(jnp.ones((rows, cols), dtype)),),
            k_lo=k_lo, k_hi=k_hi, nbytes=2 * big, ws=2 * big,
        ))

    # config 4: reduce_scatter_block — the same reduction kernel at a
    # ZeRO-ish 128 MiB gradient-shard size (3 x 128 MiB working set
    # cannot be on-chip-resident: this line must be an HBM number)
    rs_size = 128 * MiB if on_tpu else 2 * MiB
    elems = rs_size // 4
    rows, cols, blk = _sweep_geom(elems)
    loop = pallas_op.make_axpy_loop(rows, cols, blk_rows=blk)
    k_lo, k_hi = _ks(3 * rs_size, on_tpu)
    specs.append(dict(
        name="reduce_scatter_block_f32", loop=loop,
        args=(put(jnp.ones((rows, cols), jnp.float32)),),
        k_lo=k_lo, k_hi=k_hi, nbytes=3 * rs_size, ws=2 * rs_size,
    ))

    # config 5: alltoall i32 — blocked transpose (all-pairs shuffle),
    # applied twice per loop iteration = 4 streams counted (see
    # make_transpose_loop: a single non-aliased call per iteration
    # makes XLA copy the fori_loop carry back every iteration — 2N
    # uncounted bytes that capped three rounds of this line at ~0.49
    # of ceiling; the r04 probes 5-7 nailed it to aliasing alone).
    # 1024 sits exactly at the 16 MB scoped-VMEM limit (2 x 4 MB
    # buffers double-buffered), so fall back if the compiler tightens
    # it.
    tn = 8192 if on_tpu else 1024
    x = put(jnp.arange(tn * tn, dtype=jnp.int32).reshape(tn, tn))
    small = None
    last_err = None
    for t_block in (1024, 512, 256):
        if tn % t_block:
            continue
        try:
            t_loop, t_call = pallas_op.make_transpose_loop(
                tn, block=t_block
            )
            small = np.asarray(t_call(x)[:4, :4])  # compiles/executes
            break
        except Exception as e:  # scoped-VMEM tightened: smaller tile
            last_err = e
    if small is None:
        raise RuntimeError(
            f"no transpose block size compiled for n={tn}: {last_err}"
        )
    np.testing.assert_array_equal(small, np.asarray(x[:4, :4]).T)
    k_lo, k_hi = _ks(4 * tn * tn * 4, on_tpu)
    specs.append(dict(
        name="alltoall_i32_torus", loop=t_loop, args=(x,),
        k_lo=k_lo, k_hi=k_hi, nbytes=4 * tn * tn * 4,
        ws=2 * tn * tn * 4,
    ))

    # ceiling candidates: alternate copy block shapes (the primary
    # candidate is bcast_f32 above — same kernel, tuned SCALE_BLOCK).
    # Which shape wins varies session to session (+-20% wobble), so
    # the ceiling takes the per-round max over all of them.
    elems = big // 4
    for cand_name, (ar, ac) in (
        ("ceiling_copy_alt", pallas_op.SCALE_BLOCK_ALT),
        ("ceiling_copy_alt2", pallas_op.SCALE_BLOCK_ALT2),
    ):
        rows = elems // ac
        loop = pallas_op.make_scale_loop(rows, ac, blk_rows=ar)
        k_lo, k_hi = _ks(2 * big, on_tpu)
        specs.append(dict(
            name=cand_name, loop=loop,
            args=(put(jnp.ones((rows, ac), jnp.float32)),),
            k_lo=k_lo, k_hi=k_hi, nbytes=2 * big,
        ))

    # parity spot-check (BASELINE metric demands result parity): the
    # op component's axpy against numpy
    a = np.random.default_rng(0).standard_normal((64, 256)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((64, 256)).astype(np.float32)
    got = np.asarray(pallas_op.axpy(jnp.asarray(a), jnp.asarray(b), 0.5))
    np.testing.assert_allclose(got, b * 0.5 + a, rtol=1e-6)

    return specs, ("bcast_f32", "ceiling_copy_alt", "ceiling_copy_alt2")


#: bf16 matmul peak by device kind substring (published chip specs);
#: unknown kinds report achieved FLOP/s with mfu null rather than a
#: made-up ratio
PEAK_FLOPS = (
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v4", 275e12), ("v6", 918e12),
)


def _mfu_metric(jax, jnp, dev, on_tpu, rounds):
    """Compute-bound line: the flagship transformer's fwd+bwd step on
    one chip (tiny-but-MXU-shaped dims), slope-timed like every other
    loop, FLOPs taken from XLA's own cost analysis. Every other bench
    config is memory-bound, so without this a regression in the
    compute path (e.g. ops/pallas_attention.py) would be invisible to
    the round record."""
    from jax import lax

    from ompi_release_tpu.models import transformer as tfm
    from ompi_release_tpu.parallel.mesh_axes import build_parallel_mesh

    if on_tpu:
        cfg = tfm.ModelConfig(
            vocab=2048, d_model=512, n_layers=4, n_heads=8, head_dim=64,
            d_ff=2048, max_seq=256, dtype=jnp.bfloat16,
        )
        b, s = 8, 256
    else:  # CI-sized
        cfg = tfm.ModelConfig(
            vocab=128, d_model=64, n_layers=2, n_heads=4, head_dim=16,
            d_ff=128, max_seq=32, dtype=jnp.float32,
        )
        b, s = 2, 32
    mesh = build_parallel_mesh(devices=[dev])
    params = tfm.shard_params(
        tfm.init_params(jax.random.PRNGKey(0), cfg), cfg, mesh
    )
    fwd = tfm.make_forward(cfg, mesh)
    rng = np.random.RandomState(0)
    tok = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab, size=(b, s), dtype=np.int32)),
        dev,
    )
    tgt = jnp.roll(tok, -1, axis=1)
    grad_fn = jax.value_and_grad(lambda p: fwd(p, tok, tgt))

    def loop(params, k):
        def body(_, p):
            _, g = grad_fn(p)
            # inline SGD keeps every iteration's bwd live (no folding)
            return jax.tree.map(
                lambda a, d: a - jnp.asarray(1e-6, a.dtype)
                * d.astype(a.dtype), p, g)
        p = lax.fori_loop(0, k, body, params)
        return jnp.sum(jax.tree.leaves(p)[0].astype(jnp.float32))

    loop = jax.jit(loop)

    # FLOPs per fwd+bwd step from the compiler, not a hand formula
    flops_per_step = None
    try:
        ca = jax.jit(grad_fn).lower(params).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops_per_step = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass

    k_lo, k_hi = _calibrate_k(loop, (params,), 258) if on_tpu else (2, 10)
    # warm both K programs, then slope-time like the bandwidth lines
    _sync(loop(params, k_lo))
    _sync(loop(params, k_hi))
    slopes, lo_t, hi_t = [], [], []
    for _ in range(rounds):
        tlo = _timed(loop, (params,), k_lo)
        thi = _timed(loop, (params,), k_hi)
        lo_t.append(tlo)
        hi_t.append(thi)
        slopes.append(max((thi - tlo) / (k_hi - k_lo), 1e-12))
    sec_per_step = float(np.median(slopes))

    entry = {
        "metric": "transformer_fwdbwd_step", "unit": "TFLOP/s",
        "sec_per_step": round(sec_per_step, 6),
        "vs_baseline": None,
    }
    # same jitter gate as _run_rounds: a K-delta inside per-call host
    # jitter is noise — flag it rather than report a confident
    # garbage MFU
    if on_tpu and (np.median(hi_t) - np.median(lo_t)) < 0.05:
        entry.update(value=None, mfu=None, unstable=True,
                     note="K-delta inside host jitter; unreliable")
        return entry
    if flops_per_step is None:
        entry["value"] = None
        entry["note"] = "XLA cost analysis unavailable on this backend"
        return entry
    achieved = flops_per_step / sec_per_step
    entry["value"] = round(achieved / 1e12, 3)
    entry["flops_per_step"] = flops_per_step
    kind = dev.device_kind.lower()
    peak = next((p for sub, p in PEAK_FLOPS if sub in kind), None)
    if peak is not None and on_tpu:
        entry["mfu"] = round(achieved / peak, 4)
        entry["peak_tflops"] = peak / 1e12
        entry["device_kind"] = dev.device_kind
    else:
        entry["mfu"] = None
    return entry


def _mesh_specs(jax, jnp, devices, on_tpu):
    """The 5 configs as real SPMD collectives over the device mesh,
    using the framework's coll/spmd kernels.

    No spec here carries a ``ws`` key ON PURPOSE: the on-chip tier
    label exists for single-chip op loops whose whole working set can
    sit in VMEM; a collective always crosses the interconnect, so
    every mesh line is ineligible (the gate's missing-ws default) and
    reports a real ratio."""
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_release_tpu.coll import spmd
    from ompi_release_tpu.ops import op as ops_mod
    from ompi_release_tpu.ops import pallas_op
    from ompi_release_tpu.parallel.mesh_axes import vary_over

    n = len(devices)
    mesh = Mesh(np.array(devices), ("rank",))
    sh = NamedSharding(mesh, P("rank"))
    specs = []

    def coll_loop(body_fn):
        @partial(jax.jit, static_argnums=1)
        def loop(x, k):
            def spmd_body(b):
                # psum-style outputs are rank-INvariant in shard_map's
                # varying-axes type system; the loop carry must stay
                # varying to match its input type (ppermute outputs
                # are already varying — vary_over leaves those alone)
                def body(i, a):
                    return vary_over(body_fn(a), ("rank",))

                acc = lax.fori_loop(0, k, body, b)
                flat = acc.reshape(-1)
                return (flat[0] + flat[-1])[None]

            s = jax.shard_map(spmd_body, mesh=mesh, in_specs=P("rank"),
                              out_specs=P("rank"))(x)
            return s[0]

        return loop

    inv_n = np.float32(1.0 / n)

    # config 1: ring — one ppermute hop per iteration (token ring)
    perm = [(i, (i + 1) % n) for i in range(n)]
    ring = coll_loop(lambda a: lax.ppermute(a, "rank", perm))
    tok = jax.device_put(jnp.zeros((n, 128), jnp.float32), sh)
    k_lo, k_hi = _ks(0, on_tpu) if on_tpu else (2, 34)
    specs.append(dict(name="ring_4hop", loop=ring, args=(tok,),
                      k_lo=k_lo, k_hi=k_hi, nbytes=None, hops=1))

    # config 2: allreduce sweep (psum = coll/xla's lowering)
    sweep = SWEEP_BYTES if on_tpu else SWEEP_BYTES[:3]
    for size in sweep:
        elems = max(n, size // 4)
        x = jax.device_put(jnp.ones((elems,), jnp.float32), sh)
        loop = coll_loop(
            lambda a: spmd.allreduce_lax(a, ops_mod.SUM, "rank") * inv_n
        )
        k_lo, k_hi = _ks(2 * size, on_tpu)
        specs.append(dict(
            name=f"allreduce_{_human(size)}", loop=loop, args=(x,),
            k_lo=k_lo, k_hi=k_hi, size=size,
            nbytes=int(2 * (n - 1) / n * size),  # ring bus traffic
        ))

    big = 256 * MiB if on_tpu else 2 * MiB
    belems = max(n, big // 4)

    # config 3: bcast f32 + allgather bf16
    xb = jax.device_put(jnp.ones((belems,), jnp.float32), sh)
    bcast = coll_loop(
        lambda a: spmd.bcast_masked_psum(a, a.dtype, "rank", 0)
    )
    k_lo, k_hi = _ks(2 * big, on_tpu)
    specs.append(dict(name="bcast_f32", loop=bcast, args=(xb,),
                      k_lo=k_lo, k_hi=k_hi, nbytes=big))
    xg = jax.device_put(jnp.ones((belems,), jnp.bfloat16), sh)
    gather = coll_loop(
        lambda a: lax.all_gather(a, "rank")[lax.axis_index("rank")]
    )
    specs.append(dict(name="allgather_bf16", loop=gather, args=(xg,),
                      k_lo=k_lo, k_hi=k_hi,
                      nbytes=int((n - 1) / n * big * 2 // 2)))

    # config 4: reduce_scatter_block (psum_scatter lowering; the tile
    # rebuilding the loop carry adds local HBM traffic — reported bw
    # is collective bytes only, see docstring)
    seg = belems // n
    xr = jax.device_put(jnp.ones((n * seg,), jnp.float32), sh)
    rs = coll_loop(
        lambda a: jnp.tile(
            spmd.reduce_scatter_lax(a, ops_mod.SUM, "rank", n) * inv_n, n
        )
    )
    specs.append(dict(name="reduce_scatter_block_f32", loop=rs,
                      args=(xr,), k_lo=k_lo, k_hi=k_hi,
                      nbytes=int((n - 1) / n * 4 * n * seg)))

    # config 5: alltoall int32 on a 2-D torus (two-phase x then y),
    # falling back to 1-D when n has no 2-D factorization
    a_ax = 2 if n % 2 == 0 and n > 2 else 1
    if a_ax > 1:
        mesh2 = Mesh(np.array(devices).reshape(a_ax, n // a_ax),
                     ("x", "y"))

        @partial(jax.jit, static_argnums=1)
        def a2a(x, k):
            def spmd_body(b):
                def body(i, acc):
                    acc = lax.all_to_all(acc, "x", 0, 0, tiled=True)
                    return lax.all_to_all(acc, "y", 0, 0, tiled=True)

                acc = lax.fori_loop(0, k, body, b)
                flat = acc.reshape(-1)
                return (flat[0] + flat[-1])[None]

            from jax.sharding import PartitionSpec as P2
            s = jax.shard_map(spmd_body, mesh=mesh2,
                              in_specs=P2(("x", "y")),
                              out_specs=P2(("x", "y")))(x)
            return s[0]

        xa = jax.device_put(
            jnp.ones((belems,), jnp.int32),
            NamedSharding(mesh2, jax.sharding.PartitionSpec(("x", "y"))),
        )
        specs.append(dict(name="alltoall_i32_torus", loop=a2a,
                          args=(xa,), k_lo=k_lo, k_hi=k_hi,
                          nbytes=int(2 * (n - 1) / n * big)))
    else:
        xa = jax.device_put(jnp.ones((belems,), jnp.int32), sh)
        a2a = coll_loop(lambda a: spmd.alltoall_lax(
            a.reshape(n, -1), "rank", n).reshape(-1))
        specs.append(dict(name="alltoall_i32_torus", loop=a2a,
                          args=(xa,), k_lo=k_lo, k_hi=k_hi,
                          nbytes=int((n - 1) / n * big)))

    # ceiling: single-device HBM copy (placeholder for an ICI-bandwidth
    # ceiling until multi-chip hardware is available — documented, not
    # hidden: collective busbw vs one chip's copy bw)
    csize = 16 * MiB if on_tpu else MiB
    elems = csize // 4
    cols = 2048
    loop = pallas_op.make_scale_loop(elems // cols, cols)
    k_lo, k_hi = _ks(2 * csize, on_tpu)
    specs.append(dict(
        name="ceiling_copy", loop=loop,
        args=(jax.device_put(jnp.ones((elems // cols, cols),
                                      jnp.float32), devices[0]),),
        k_lo=k_lo, k_hi=k_hi, nbytes=2 * csize,
    ))

    # parity: psum of ones over the mesh == n on every shard
    ones = jax.device_put(jnp.ones((n,), jnp.float32), sh)
    got = jax.shard_map(
        lambda b: spmd.allreduce_lax(b, ops_mod.SUM, "rank"),
        mesh=mesh, in_specs=jax.sharding.PartitionSpec("rank"),
        out_specs=jax.sharding.PartitionSpec("rank"))(ones)
    np.testing.assert_allclose(np.asarray(got), np.full(n, n), rtol=0)

    return specs, ("ceiling_copy",)


def _pvar_snapshot():
    """Current pvar values, JSON-ready (per-config observability)."""
    try:
        import ompi_release_tpu.obs  # noqa: F401  journal pvars exist
        from ompi_release_tpu.mca import pvar as _pvar_mod

        return _pvar_mod.PVARS.read_all()
    except Exception:
        return {}


#: pvars the coll micro-suite labels its lines with (segment counts,
#: fusion savings, plan-cache behaviour — the PR-goal observables)
_MICRO_PVARS = (
    "coll_pipeline_segments", "coll_fusion_batched",
    "coll_fusion_flushes", "coll_fusion_bytes_saved",
    "coll_programs_compiled", "coll_invocations",
    "coll_plan_cache_hits", "coll_compiled_cache_hits",
    "coll_orchestration_seconds",
    "obs_sample_overhead_seconds", "obs_series_points",
    "obs_sample_ticks",
)


def _micro_pvars():
    from ompi_release_tpu.mca import pvar as _pvar_mod

    out = {}
    for name in _MICRO_PVARS:
        pv = _pvar_mod.PVARS.lookup(name)
        if pv is not None:
            out[name] = pv.read()
    return out


def _coll_micro_suite():
    """coll_pipeline / coll_fusion micro-suite through the framework's
    own driver (not raw meshes): a ≥1 MiB pipelined allreduce + bcast
    and a 64-small-tensors fusion burst, one JSON line each, every
    line labelled with the cumulative pvar snapshot so BENCH_* files
    capture segment counts and fusion savings. The fusion line's
    device_collectives < tensors_fused check is pvar-based, so it
    holds on the CPU backend too."""
    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import var as mca_var

    lines = []
    world = mpi.init()

    # -- pipeline case: 1 MiB/rank allreduce + bcast, 256 KiB segments
    mca_var.set_value("coll", "tuned")
    try:
        tuned = world.dup(name="bench_pipe")
    finally:
        mca_var.VARS.unset("coll")
    elems = MiB // 4
    x = np.ones((world.size, elems), np.float32)
    try:
        mca_var.set_value("coll_tuned_allreduce_algorithm", "ring")
        mca_var.set_value("coll_tuned_bcast_algorithm", "binomial")
        mca_var.set_value("coll_pipeline_segsize", 256 * 1024)
        for name, call in (
            ("coll_pipeline_allreduce_1MiB",
             lambda: tuned.allreduce(x)),
            ("coll_pipeline_bcast_1MiB",
             lambda: tuned.bcast(x, root=0)),
        ):
            _sync(call())  # compile + prime the plan cache
            t0 = time.perf_counter()
            reps = 3
            for _ in range(reps):
                _sync(call())
            dt = (time.perf_counter() - t0) / reps
            lines.append({
                "metric": name, "value": round(MiB / dt / 1e9, 4),
                "unit": "GB/s", "vs_baseline": None,
                "suite": "coll_pipeline", "seconds": round(dt, 6),
                "pvars": _micro_pvars(), "cumulative": True,
            })
        # -- sampled-overhead case: the SAME 1 MiB allreduce with the
        # continuous metrics plane armed (obs + sampler at a busy
        # 50 ms interval). The ratio line is the <2%-overhead claim
        # measured in situ, with the obs_sample_overhead_seconds pvar
        # delta as the sampler's own accounting of where time went.
        import ompi_release_tpu.obs as _obs_pkg
        from ompi_release_tpu.obs import sampler as _sampler
        from ompi_release_tpu.runtime.runtime import Runtime as _Rt

        from ompi_release_tpu.mca import pvar as _pvar_mod

        def _ov():
            pv = _pvar_mod.PVARS.lookup("obs_sample_overhead_seconds")
            return float(pv.read()) if pv is not None else 0.0

        call = lambda: tuned.allreduce(x)
        reps = 5
        _sync(call())
        t0 = time.perf_counter()
        for _ in range(reps):
            _sync(call())
        base_dt = (time.perf_counter() - t0) / reps
        was_enabled = _obs_pkg.enabled
        ov0 = _ov()
        _obs_pkg.enable()
        mca_var.set_value("obs_sample_interval", 0.05)
        _sampler.SAMPLER.start(0.05, runtime=_Rt._instance)
        try:
            _sync(call())
            t0 = time.perf_counter()
            for _ in range(reps):
                _sync(call())
            samp_dt = (time.perf_counter() - t0) / reps
        finally:
            _sampler.stop(final_push=False)
            if not was_enabled:
                _obs_pkg.disable()
            mca_var.VARS.unset("obs_sample_interval")
        lines.append({
            "metric": "coll_pipeline_allreduce_1MiB_sampled",
            "value": round(base_dt / max(samp_dt, 1e-9), 4),
            "unit": "x_vs_sampled_run", "vs_baseline": None,
            "suite": "coll_pipeline",
            "seconds": round(samp_dt, 6),
            "unsampled_seconds": round(base_dt, 6),
            "sampler_overhead_s": round(_ov() - ov0, 6),
            "pvars": _micro_pvars(), "cumulative": True,
        })
    finally:
        mca_var.VARS.unset("coll_tuned_allreduce_algorithm")
        mca_var.VARS.unset("coll_tuned_bcast_algorithm")
        mca_var.VARS.unset("coll_pipeline_segsize")
        tuned.free()

    # -- fusion case: 64 small tensors through the fusion buffer
    from ompi_release_tpu.mca import pvar as _pvar_mod

    def _counter(name):
        pv = _pvar_mod.PVARS.lookup(name)
        return float(pv.read()) if pv is not None else 0.0

    b0, f0 = _counter("coll_fusion_batched"), _counter("coll_fusion_flushes")
    fb = world.fusion_buffer()
    tensors = 64
    small = [np.full((world.size, 256), i, np.float32)
             for i in range(tensors)]
    t0 = time.perf_counter()
    handles = [fb.allreduce(s) for s in small]
    fb.flush()
    vals = [h.result() for h in handles]
    dt = time.perf_counter() - t0
    np.testing.assert_allclose(
        np.asarray(vals[3][0]), np.full(256, 3.0 * world.size), rtol=0
    )
    fused = int(_counter("coll_fusion_batched") - b0)
    issued = int(_counter("coll_fusion_flushes") - f0)
    lines.append({
        "metric": "coll_fusion_64x1KiB", "value": issued, "unit":
        "device_collectives", "vs_baseline": None,
        "suite": "coll_fusion", "tensors_fused": fused,
        "fewer_collectives_than_tensors": issued < fused,
        "seconds": round(dt, 6),
        "pvars": _micro_pvars(), "cumulative": True,
    })
    return lines  # main()'s emit() stamps the tier label


def _steady_state_micro_suite():
    """Interpreted-vs-compiled steady state (the compiled whole-
    schedule plan layer, coll/plan): the SAME collective at 4 KiB–
    1 MiB run through the fully interpreted per-call dispatch
    (``coll_compiled=0``) and through frozen compiled plans, one-shot
    blocking AND MPI-4 persistent. Python-orchestration time is
    separated from device/wire time two ways that must agree: the
    ``coll_orchestration_seconds`` pvar delta (the dispatch path's own
    accounting, the acceptance witness) and wall − (wall − orch).
    Every compiled leg asserts BITWISE parity against its interpreted
    twin in-app before a single line is emitted — the plans fire the
    very programs the interpreted path compiled, so this is a
    structural identity being spot-checked, not a tolerance."""
    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import pvar as _pvar_mod
    from ompi_release_tpu.mca import var as mca_var

    world = mpi.init()
    lines = []
    KiB = 1024
    # the tuned component's pipelined/segmented schedules are the
    # documented per-call Python overhead (ring segments, binomial
    # segment trees, per-dispatch decision rules) — the comparison the
    # compiled plans exist to win. Force them for both legs.
    mca_var.set_value("coll", "tuned")
    try:
        tuned_i = world.dup(name="steady_interp")
        tuned_c = world.dup(name="steady_comp")
    finally:
        mca_var.VARS.unset("coll")
    mca_var.set_value("coll_tuned_allreduce_algorithm", "ring")
    mca_var.set_value("coll_tuned_bcast_algorithm", "binomial")
    mca_var.set_value("coll_pipeline_segsize", 64 * KiB)

    def _orch():
        pv = _pvar_mod.PVARS.lookup("coll_orchestration_seconds")
        return float(pv.read()) if pv is not None else 0.0

    def _hits():
        pv = _pvar_mod.PVARS.lookup("coll_compiled_cache_hits")
        return pv.read() if pv is not None else {"sum": 0, "count": 0}

    reps = 30
    cases = [("allreduce", 4 * KiB), ("allreduce", 256 * KiB),
             ("allreduce", MiB), ("bcast", 256 * KiB),
             ("allgather", 256 * KiB)]
    try:
        _steady_cases(cases, reps, world, tuned_i, tuned_c, lines,
                      _orch, _hits, mca_var)
    finally:
        mca_var.VARS.unset("coll_tuned_allreduce_algorithm")
        mca_var.VARS.unset("coll_tuned_bcast_algorithm")
        mca_var.VARS.unset("coll_pipeline_segsize")
        tuned_i.free()
        tuned_c.free()

    # spanning leg: a real 3-process loopback job fires the SAME
    # 256 KiB allreduce interpreted vs through frozen wire plans
    # (precomposed round structure + frame headers) vs through frozen
    # plans WITH the obs plane on (the flight-recorder leg — the
    # "tracing never de-optimizes the hot path" acceptance factor);
    # orchestration is the posting+dispatch pvar delta, parity and
    # plan-replay (cache-hit deltas) asserted in-app. The obs leg
    # leaves ledger-p*.json dumps behind which tpu-doctor must expand
    # into cross-process flow arrows — checked host-side below.
    import os
    import tempfile

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    dump_dir = tempfile.mkdtemp(prefix="steady_obs_")
    doc = run_loopback_app(
        3, _STEADY_SPAN_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__)), "dump": dump_dir}, {},
        "steady_span.json", timeout_s=280)
    if doc is None:
        lines.append({
            "metric": "steady_spanning_suite", "value": None,
            "unit": None, "vs_baseline": None,
            "error": "loopback job failed"})
    else:
        for ln in doc["lines"]:
            ln.setdefault("suite", "steady_state")
            ln.setdefault("vs_baseline", None)
            lines.append(ln)
        lines.append(_steady_obs_trace_line(dump_dir))
    return lines


def _steady_obs_trace_line(dump_dir):
    """Host-side check of the obs leg's flight-recorder dumps: doctor
    must expand the per-rank binary rings against the frozen plan
    metadata into synthetic spans whose flow ids PAIR across ranks
    (the merged-trace arrows). Informational metric (no gate prefix);
    the hard signal is paired_flows > 0."""
    from ompi_release_tpu.obs import doctor as _doctor

    line = {"metric": "obs_ledger_trace_spanning_allreduce_256KiB",
            "unit": None, "vs_baseline": None, "suite": "steady_state"}
    try:
        dumps = _doctor.load_dir(dump_dir)
        ledger_spans = [s for d in dumps for s in d["spans"]
                        if s.get("ledger")]
        pairs = [p for p in _doctor.flow_pairs(dumps)
                 if p["src"].get("ledger") and p["cross_process"]]
        line.update({
            "value": len(pairs), "ledger_spans": len(ledger_spans),
            "paired_flows": len(pairs),
            "arrows_reconstructed": bool(pairs),
        })
        assert ledger_spans, "obs leg left no ledger dumps to expand"
        assert pairs, ("ledger-reconstructed sends/recvs did not pair "
                       "into cross-process flow arrows")
    except AssertionError as e:
        line.update({"value": None, "error": str(e)})
    return line


def _steady_cases(cases, reps, world, tuned_i, tuned_c, lines,
                  _orch, _hits, mca_var):
    for coll, nbytes in cases:
        elems = max(1, nbytes // 4)
        x = (np.arange(world.size * elems, dtype=np.float32)
             .reshape(world.size, elems) * 0.5)
        label = f"{coll}_{_human(nbytes)}"

        def call(comm, _c=coll, _x=x):
            if _c == "allreduce":
                return comm.allreduce(_x)
            if _c == "bcast":
                return comm.bcast(_x, root=0)
            return comm.allgather(_x)

        def timed_leg(comm):
            _sync(call(comm))  # warm: compile / freeze the plan
            o0 = _orch()
            t0 = time.perf_counter()
            for _ in range(reps):
                _sync(call(comm))
            wall = (time.perf_counter() - t0) / reps
            orch = (_orch() - o0) / reps
            return wall, orch, np.asarray(call(comm))

        mca_var.set_value("coll_compiled", 0)
        try:
            wall_i, orch_i, want = timed_leg(tuned_i)
        finally:
            mca_var.VARS.unset("coll_compiled")

        h0 = _hits()
        wall_c, orch_c, got = timed_leg(tuned_c)
        h1 = _hits()
        np.testing.assert_array_equal(got, want)  # BITWISE in-app
        assert h1["sum"] - h0["sum"] >= reps, (
            "compiled leg did not fire frozen plans")
        wall_p = orch_p = None
        if coll == "allreduce":
            # MPI-4 persistent: start() re-fires the same frozen
            # plan the blocking calls froze (signature memoized at
            # *_init — start() builds nothing)
            req = tuned_c.allreduce_init(x)
            req.start(); req.wait()
            o0 = _orch()
            t0 = time.perf_counter()
            for _ in range(reps):
                req.start()
                req.wait()
            wall_p = (time.perf_counter() - t0) / reps
            orch_p = (_orch() - o0) / reps
            np.testing.assert_array_equal(np.asarray(req.value), want)

        common = {
            "suite": "steady_state", "vs_baseline": None,
            "reps": reps, "bytes": nbytes,
        }
        lines.append({
            "metric": f"steady_orch_{label}_interpreted",
            "value": round(orch_i, 9), "unit": "s",
            "wall_seconds": round(wall_i, 9),
            "comm_alone_seconds": round(wall_i - orch_i, 9), **common,
        })
        lines.append({
            "metric": f"steady_orch_{label}_compiled",
            "value": round(orch_c, 9), "unit": "s",
            "wall_seconds": round(wall_c, 9),
            "comm_alone_seconds": round(wall_c - orch_c, 9), **common,
        })
        lines.append({
            "metric": f"compiled_{label}_orch_speedup",
            "value": round(orch_i / max(orch_c, 1e-12), 3),
            "unit": "x_orchestration",
            "interpreted_orch_s": round(orch_i, 9),
            "compiled_orch_s": round(orch_c, 9),
            "wall_speedup": round(wall_i / max(wall_c, 1e-12), 3),
            **common,
        })
        if wall_p is not None:
            lines.append({
                "metric": f"steady_orch_{label}_persistent",
                "value": round(orch_p, 9), "unit": "s",
                "wall_seconds": round(wall_p, 9), **common,
            })


def _rma_steady_micro_suite():
    """Interpreted-vs-planned steady state for the one-sided plane
    (the RMA analogue of the coll steady-state suite, osc/plan): the
    SAME fence epoch — put + accumulate + get on a driver window — run
    through the fully interpreted per-epoch dispatch
    (``osc_compiled=0``) and through frozen access plans whose single
    fused XLA program replays per epoch. Python-orchestration time is
    the ``osc_orchestration_seconds`` pvar delta (both paths feed it);
    the planned leg asserts BITWISE parity against its interpreted
    twin in-app (same branch lambdas, so structural identity) and that
    ``osc_plan_cache_hits`` recorded >= reps replays. A second block
    does the same for the planned symmetric-heap bulk path
    (``shmem_bulk``): batched puts/AMOs drained as one window epoch
    per quiet vs the per-call epochs, wall-time compared with parity
    on every PE's final heap contents."""
    import jax.numpy as jnp

    import ompi_release_tpu as mpi
    from ompi_release_tpu import ops
    # eager: osc/plan is lazily imported by the window close path, and
    # its pvars only exist after module import — baseline reads below
    # need them registered NOW
    import ompi_release_tpu.osc.plan  # noqa: F401
    from ompi_release_tpu.mca import pvar as _pvar_mod
    from ompi_release_tpu.mca import var as mca_var
    from ompi_release_tpu.osc import win_allocate
    from ompi_release_tpu.oshmem import shmem as _shmem_mod

    world = mpi.init()
    lines = []
    KiB = 1024
    reps = 30

    def _orch():
        pv = _pvar_mod.PVARS.lookup("osc_orchestration_seconds")
        return float(pv.read()) if pv is not None else 0.0

    def _hits():
        pv = _pvar_mod.PVARS.lookup("osc_plan_cache_hits")
        return pv.read() if pv is not None else {"sum": 0, "count": 0}

    for nbytes in (4 * KiB, 64 * KiB, 256 * KiB):
        elems = max(1, nbytes // 4)
        label = f"rma_fence_{_human(nbytes)}"
        pay = np.arange(elems, dtype=np.float32) * 0.5
        acc = np.full(elems, 0.25, np.float32)

        def epoch(win, _pay=pay, _acc=acc):
            win.fence()
            win.put(_pay, target=1)
            win.accumulate(_acc, target=1, op=ops.SUM)
            g = win.get(target=1)
            win.fence_end()
            return np.asarray(g.value)

        def leg(win):
            epoch(win)  # warm: freeze the plan / compile branches
            o0 = _orch()
            t0 = time.perf_counter()
            for _ in range(reps):
                out = epoch(win)
            wall = (time.perf_counter() - t0) / reps
            orch = (_orch() - o0) / reps
            return wall, orch, out, np.asarray(win.read())

        win_i = win_allocate(world, (elems,), jnp.float32)
        win_c = win_allocate(world, (elems,), jnp.float32)
        try:
            mca_var.set_value("osc_compiled", 0)
            try:
                wall_i, orch_i, got_i, data_i = leg(win_i)
            finally:
                mca_var.VARS.unset("osc_compiled")
            h0 = _hits()
            wall_c, orch_c, got_c, data_c = leg(win_c)
            h1 = _hits()
            np.testing.assert_array_equal(got_c, got_i)  # BITWISE
            np.testing.assert_array_equal(data_c, data_i)
            assert h1["sum"] - h0["sum"] >= reps, (
                "planned leg did not replay frozen epoch plans")
        finally:
            win_i.free()
            win_c.free()

        common = {"suite": "steady_state", "vs_baseline": None,
                  "reps": reps, "bytes": nbytes}
        lines.append({
            "metric": f"steady_{label}_interpreted",
            "value": round(orch_i, 9), "unit": "s",
            "wall_seconds": round(wall_i, 9),
            "comm_alone_seconds": round(wall_i - orch_i, 9), **common,
        })
        lines.append({
            "metric": f"steady_{label}_planned",
            "value": round(orch_c, 9), "unit": "s",
            "wall_seconds": round(wall_c, 9),
            "comm_alone_seconds": round(wall_c - orch_c, 9), **common,
        })
        lines.append({
            "metric": f"compiled_{label}_orch_speedup",
            "value": round(orch_i / max(orch_c, 1e-12), 3),
            "unit": "x_orchestration",
            "interpreted_orch_s": round(orch_i, 9),
            "planned_orch_s": round(orch_c, 9),
            "wall_speedup": round(wall_i / max(wall_c, 1e-12), 3),
            **common,
        })

    # planned symmetric-heap bulk path: per-call epochs vs one drained
    # window epoch per quiet, same op stream, parity on every PE
    shmem = _shmem_mod.shmem_init()

    def _bulk_ops():
        pv = _pvar_mod.PVARS.lookup("shmem_bulk_ops")
        return float(pv.read()) if pv is not None else 0.0

    for nbytes in (4 * KiB, 64 * KiB):
        elems = max(1, nbytes // 4)
        label = f"shmem_put_{_human(nbytes)}"
        vals = [np.full(elems, float(pe + 1), np.float32)
                for pe in range(shmem.n_pes)]
        bump = np.full(elems, 0.5, np.float32)

        def leg():
            sym = shmem.malloc((elems,), jnp.float32)
            try:
                for pe in range(shmem.n_pes):  # warm
                    shmem.put(sym, vals[pe], pe=pe)
                shmem.quiet()
                t0 = time.perf_counter()
                for _ in range(reps):
                    for pe in range(shmem.n_pes):
                        shmem.put(sym, vals[pe], pe=pe)
                        shmem.atomic_add(sym, bump, pe=pe)
                    shmem.quiet()
                wall = (time.perf_counter() - t0) / reps
                out = np.stack([np.asarray(shmem.get(sym, pe=pe))
                                for pe in range(shmem.n_pes)])
            finally:
                sym.free()
            return wall, out

        mca_var.set_value("shmem_bulk", 0)
        try:
            wall_p, want = leg()
        finally:
            mca_var.VARS.unset("shmem_bulk")
        b0 = _bulk_ops()
        wall_b, got = leg()
        assert _bulk_ops() - b0 >= reps, (
            "bulk leg did not route through the planned heap path")
        np.testing.assert_array_equal(got, want)  # BITWISE in-app

        common = {"suite": "steady_state", "vs_baseline": None,
                  "reps": reps, "bytes": nbytes}
        lines.append({
            "metric": f"steady_{label}_percall",
            "value": round(wall_p, 9), "unit": "s", **common,
        })
        lines.append({
            "metric": f"steady_{label}_bulk",
            "value": round(wall_b, 9), "unit": "s", **common,
        })
        lines.append({
            "metric": f"compiled_{label}_bulk_speedup",
            "value": round(wall_p / max(wall_b, 1e-12), 3),
            "unit": "x_wall", **common,
        })
    return lines


_STEADY_SPAN_APP = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime.runtime import Runtime

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

world = mpi.init()
elems = (256 * 1024) // 4
x = np.stack([np.arange(elems, dtype=np.float32) * 0.25
              for _ in range(len(world.local_comm_ranks))])
reps = 10

def leg():
    np.asarray(world.allreduce(x))  # warm: record/freeze or compile
    o0 = _pv("coll_orchestration_seconds")
    t0 = time.perf_counter()
    for _ in range(reps):
        out = np.asarray(world.allreduce(x))
    wall = (time.perf_counter() - t0) / reps
    orch = (_pv("coll_orchestration_seconds") - o0) / reps
    return wall, orch, out

def _hits():
    p = pvar.PVARS.lookup("coll_compiled_cache_hits")
    return p.read() if p is not None else {"sum": 0, "count": 0}

mca_var.set_value("coll_compiled", 0)
wall_i, orch_i, want = leg()
mca_var.VARS.unset("coll_compiled")
wall_c, orch_c, got = leg()
np.testing.assert_array_equal(got, want)  # BITWISE in-app

# obs-ON compiled leg: the flight recorder rides the SAME frozen
# plans — hit counter keeps advancing, results stay bitwise, and
# every fire appends one fixed-size record to the binary ledger ring
import ompi_release_tpu.obs as _obs_pkg
from ompi_release_tpu.obs import ledger as _ledger
mca_var.set_value("obs_dump_dir", %(dump)r)
_obs_pkg.enable()
h0 = _hits()
wall_o, orch_o, got_o = leg()
h1 = _hits()
np.testing.assert_array_equal(got_o, want)  # observed: still BITWISE
assert h1["sum"] - h0["sum"] >= reps, "obs-ON leg fell off the frozen plan"
recs = _ledger.records()
assert recs, "observed compiled fires must land in the ledger"
rec = recs[-1]
rec_bytes = _ledger.snapshot()["record_bytes"] + 8 * len(rec["round_ts"])

pidx = int(Runtime.current().bootstrap["process_index"])
if pidx == 0:
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump({"lines": [
            {"metric": "steady_orch_spanning_allreduce_256KiB_interpreted",
             "value": round(orch_i, 9), "unit": "s",
             "wall_seconds": round(wall_i, 9), "reps": reps},
            {"metric": "steady_orch_spanning_allreduce_256KiB_compiled",
             "value": round(orch_c, 9), "unit": "s",
             "wall_seconds": round(wall_c, 9), "reps": reps},
            {"metric": "compiled_spanning_allreduce_orch_speedup",
             "value": round(orch_i / max(orch_c, 1e-12), 3),
             "unit": "x_orchestration",
             "wall_speedup": round(wall_i / max(wall_c, 1e-12), 3)},
            {"metric": "steady_obs_orch_spanning_allreduce_256KiB_compiled",
             "value": round(orch_o, 9), "unit": "s",
             "wall_seconds": round(wall_o, 9), "reps": reps},
            # THE acceptance factor: obs-ON compiled leg within 1.15x
            # of the obs-OFF compiled leg (lower-better gated via the
            # steady_ prefix so the budget holds across rounds)
            {"metric": "steady_obs_overhead_spanning_allreduce_256KiB",
             "value": round(wall_o / max(wall_c, 1e-12), 3),
             "unit": "ratio", "budget": 1.15,
             "orch_ratio": round(orch_o / max(orch_c, 1e-12), 3)},
            {"metric": "ledger_record_bytes_spanning_allreduce_256KiB",
             "value": rec_bytes, "unit": "bytes",
             "wire_rounds": len(rec["round_ts"])},
        ]}, f)
mpi.finalize()
"""


def _native_rounds_micro_suite():
    """Three-way orchestration split for spanning collectives over a
    REAL 3-process loopback job: the SAME allreduce/bcast/allgather at
    4 KiB–1 MiB fired (a) fully interpreted (``coll_compiled=0``, the
    per-call dispatch), (b) through frozen wire plans replayed by the
    Python PlannedXchg loop (``coll_plan_native=0``), and (c) through
    the native C plan executor (one ctypes slice loop walks every
    round). Orchestration is the ``coll_orchestration_seconds`` pvar
    delta; every leg asserts BITWISE parity against its interpreted
    twin in-app, the native leg asserts it actually fired C-side
    (``plan_native_fires`` advanced, zero ``plan_native_fallbacks``),
    and the app asserts ``wire_native_fallback_copies`` stayed zero —
    the contiguous path never staged through a bounce buffer. THE
    acceptance factor rides ``compiled_native_allreduce_*_orch_speedup``
    (planned-replay orchestration / native orchestration, >= 2x at
    <= 256 KiB); gate directions come for free from the ``steady_``
    (lower-better) and ``compiled_`` (higher-better) prefixes."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    doc = run_loopback_app(
        3, _NATIVE_ROUNDS_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__))}, {},
        "native_rounds.json", timeout_s=420)
    if doc is None:
        return [{"metric": "native_rounds_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "loopback job failed"}]
    lines = []
    for ln in doc["lines"]:
        ln.setdefault("suite", "native_rounds")
        ln.setdefault("vs_baseline", None)
        lines.append(ln)
    return lines


_NATIVE_ROUNDS_APP = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime.runtime import Runtime

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

world = mpi.init()
L = len(world.local_comm_ranks)
# recursive doubling freezes to a byte-provable plan (the ring
# algorithm's mid-round partial mutations withdraw to PlannedXchg --
# that selection is the fallback contract, not a failure)
mca_var.set_value("hier_inter_algorithm", "recursive_doubling")
reps = 8
KiB = 1024
cases = [("allreduce", 4 * KiB), ("allreduce", 256 * KiB),
         ("allreduce", 1024 * KiB), ("bcast", 4 * KiB),
         ("bcast", 256 * KiB), ("allgather", 64 * KiB)]
lines = []

def call(coll, x):
    if coll == "allreduce":
        return np.asarray(world.allreduce(x))
    if coll == "bcast":
        return np.asarray(world.bcast(x, root=0))
    return np.asarray(world.allgather(x))

def leg(coll, x):
    call(coll, x)  # warm: record + freeze (+ native lowering)
    o0 = _pv("coll_orchestration_seconds")
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call(coll, x)
    wall = (time.perf_counter() - t0) / reps
    orch = (_pv("coll_orchestration_seconds") - o0) / reps
    return wall, orch, out

for coll, nbytes in cases:
    elems = max(1, nbytes // 4)
    x = np.stack([np.arange(elems, dtype=np.float32) * 0.25 + i
                  for i in range(L)])
    hum = ("1MiB" if nbytes >= 1024 * KiB
           else "%%dKiB" %% (nbytes // KiB))
    label = coll + "_" + hum

    mca_var.set_value("coll_compiled", 0)
    wall_i, orch_i, want = leg(coll, x)
    mca_var.VARS.unset("coll_compiled")

    mca_var.set_value("coll_plan_native", 0)
    wall_p, orch_p, got_p = leg(coll, x)
    mca_var.VARS.unset("coll_plan_native")

    f0, fb0 = _pv("plan_native_fires"), _pv("plan_native_fallbacks")
    wall_n, orch_n, got_n = leg(coll, x)
    f1, fb1 = _pv("plan_native_fires"), _pv("plan_native_fallbacks")

    np.testing.assert_array_equal(got_p, want)  # BITWISE in-app
    np.testing.assert_array_equal(got_n, want)  # BITWISE in-app
    assert f1 - f0 >= reps, (
        "native leg fell back to interpreted replay: %%s" %% label)
    assert fb1 - fb0 == 0, (
        "native leg took per-fire safety fallbacks: %%s" %% label)
    speed = orch_p / max(orch_n, 1e-12)
    if coll == "allreduce" and nbytes <= 256 * KiB:
        # THE acceptance factor: the C slice loop beats the Python
        # round replay by >= 2x on orchestration at small payloads
        assert speed >= 2.0, (
            "native orchestration speedup %%.2fx < 2x at %%s"
            %% (speed, label))

    common = {"reps": reps, "bytes": nbytes}
    lines.append({"metric": "steady_native_orch_%%s_interpreted" %% label,
                  "value": round(orch_i, 9), "unit": "s",
                  "wall_seconds": round(wall_i, 9),
                  "comm_alone_seconds": round(wall_i - orch_i, 9),
                  **common})
    lines.append({"metric": "steady_native_orch_%%s_planned" %% label,
                  "value": round(orch_p, 9), "unit": "s",
                  "wall_seconds": round(wall_p, 9),
                  "comm_alone_seconds": round(wall_p - orch_p, 9),
                  **common})
    lines.append({"metric": "steady_native_orch_%%s_native" %% label,
                  "value": round(orch_n, 9), "unit": "s",
                  "wall_seconds": round(wall_n, 9),
                  "comm_alone_seconds": round(wall_n - orch_n, 9),
                  **common})
    lines.append({"metric": "compiled_native_%%s_orch_speedup" %% label,
                  "value": round(speed, 3), "unit": "x_orchestration",
                  "planned_orch_s": round(orch_p, 9),
                  "native_orch_s": round(orch_n, 9),
                  "vs_interpreted": round(orch_i / max(orch_n, 1e-12), 3),
                  "wall_speedup": round(wall_p / max(wall_n, 1e-12), 3),
                  **common})

assert _pv("wire_native_fallback_copies") == 0, (
    "contiguous native fires must not stage through bounce buffers")
lines.append({"metric": "native_rounds_pool",
              "value": _pv("plan_pool_hits"), "unit": None,
              "pool_bytes": _pv("plan_pool_bytes"),
              "native_fires": _pv("plan_native_fires"),
              "native_fallbacks": _pv("plan_native_fallbacks")})

pidx = int(Runtime.current().bootstrap["process_index"])
if pidx == 0:
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump({"lines": lines}, f)
mpi.finalize()
"""


def _sentinel_micro_suite():
    """sentinel lines: the SAME 1 MiB allreduce with the collective
    contract sentinel off (obs_sentinel=0 — one attribute check per
    collective) and on in post-hoc mode (obs_sentinel=1 — signature
    hash + journal event per collective), with the
    ``sentinel_ops_hashed`` pvar delta as the witness that the
    enabled leg really hashed every call. The obs plane is ON for
    BOTH legs so the overhead_frac isolates the sentinel's own cost
    — only the obs_sentinel cvar varies between legs. All three
    metrics gate lower-better (tpu_bench_gate: ``s`` unit /
    ``sentinel_`` prefix), so the near-zero-overhead claim is
    enforced across rounds, not asserted once."""
    import ompi_release_tpu as mpi
    import ompi_release_tpu.obs as _obs_pkg
    from ompi_release_tpu.mca import pvar as _pvar_mod
    from ompi_release_tpu.mca import var as mca_var
    from ompi_release_tpu.obs import sentinel as _sentinel

    world = mpi.init()
    elems = MiB // 4
    x = np.ones((world.size, elems), np.float32)
    call = lambda: world.allreduce(x)  # noqa: E731
    reps = 5

    def timed():
        _sync(call())  # warm the plan cache outside the timing
        t0 = time.perf_counter()
        for _ in range(reps):
            _sync(call())
        return (time.perf_counter() - t0) / reps

    def _hashed():
        pv = _pvar_mod.PVARS.lookup("sentinel_ops_hashed")
        return float(pv.read()) if pv is not None else 0.0

    # the disabled leg must really BE disabled, whatever the operator
    # passed on the command line — and teardown must hand their
    # setting back, not strip it for the rest of the round
    prior = int(mca_var.get("obs_sentinel", 0) or 0)
    was_enabled = _obs_pkg.enabled
    try:
        _obs_pkg.enable()  # same obs state on BOTH legs
        mca_var.set_value("obs_sentinel", 0)
        _sentinel.refresh(True)
        base_dt = timed()  # obs_sentinel=0: the provably-free leg
        mca_var.set_value("obs_sentinel", 1)
        _sentinel.refresh(True)
        h0 = _hashed()
        sent_dt = timed()
    finally:
        if prior:
            mca_var.set_value("obs_sentinel", prior)
        else:
            mca_var.VARS.unset("obs_sentinel")
        if not was_enabled:
            _obs_pkg.disable()
        else:
            _sentinel.refresh(True)
    hashed = int(_hashed() - h0)
    assert hashed >= reps, (
        f"sentinel witness: expected >= {reps} hashed ops, got {hashed}")
    return [{
        "metric": "sentinel_allreduce_1MiB_disabled",
        "value": round(base_dt, 6), "unit": "s", "vs_baseline": None,
        "suite": "sentinel",
    }, {
        "metric": "sentinel_allreduce_1MiB_posthoc",
        "value": round(sent_dt, 6), "unit": "s", "vs_baseline": None,
        "suite": "sentinel", "ops_hashed": hashed,
    }, {
        "metric": "sentinel_allreduce_overhead_frac",
        "value": round(sent_dt / max(base_dt, 1e-9) - 1.0, 4),
        "unit": "frac_overhead", "vs_baseline": None,
        "suite": "sentinel", "ops_hashed": hashed,
        "disabled_seconds": round(base_dt, 6),
        "enabled_seconds": round(sent_dt, 6),
    }]


#: worker app for the wire micro-suite: a REAL 3-process tpurun job on
#: the CPU mesh (the wire is host-side regardless of accelerator), so
#: the emitted numbers exercise the exact envelope/fragment/lane code
#: a multi-controller job runs. Process 0 writes its JSON lines to
#: OMPITPU_WIRE_BENCH_OUT; the parent re-emits them as bench lines.
_WIRE_BENCH_APP = r'''
import json, os, sys, threading, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# distinct shm identity per worker: every byte rides the DCN staged
# path — the fragment pipeline under measurement (shm handoffs are a
# single segment memcpy and would hide it)
os.environ["OMPITPU_HOST_ID"] = (
    "wirebench-" + os.environ["OMPITPU_NODE_ID"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime.runtime import Runtime

SIZES = json.loads(os.environ["OMPITPU_WIRE_BENCH_SIZES"])
HOL_MIB = int(os.environ.get("OMPITPU_WIRE_BENCH_HOL_MIB", "8"))
AGV_MIB = int(os.environ.get("OMPITPU_WIRE_BENCH_AGV_MIB", "1"))
world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
lines = []

def _hol():
    pv = pvar.PVARS.lookup("wire_hol_wait_seconds")
    return float(pv.read()) if pv is not None else 0.0

# -- p2p ping-pong bandwidth (rank 1 in p0 <-> rank 3 in p1) ---------------
for size in SIZES:
    x = np.ones(max(1, size // 4), np.float32)
    best = None
    for _ in range(3):
        world.barrier()
        if me == 0:
            t0 = time.perf_counter()
            world.send(x, 3, tag=11, rank=1)
            v, _st = world.recv(source=3, tag=12, rank=1)
            dt = time.perf_counter() - t0
            assert np.asarray(v).shape == x.shape
            best = dt if best is None else min(best, dt)
        elif me == 1:
            v, _st = world.recv(source=1, tag=11, rank=3)
            world.send(np.asarray(v), 1, tag=12, rank=3)
    if me == 0:
        lines.append({
            "metric": "wire_p2p_%%dMiB" %% (size >> 20),
            "value": round(2 * size / best / 1e9, 4), "unit": "GB/s",
            "vs_baseline": None, "suite": "wire", "rtt_s": round(best, 5),
        })

# -- two concurrent large transfers, distinct tags: lanes 4 vs 1 -----------
hol_size = HOL_MIB << 20
xh = np.ones(hol_size // 4, np.float32)
for lanes in (4, 1):
    mca_var.set_value("wire_p2p_lanes", lanes)
    world.barrier()
    h0 = _hol()
    world.barrier()
    if me == 0:
        t0 = time.perf_counter()
        ts = [threading.Thread(target=lambda: world.send(xh, 3, tag=1,
                                                         rank=0)),
              threading.Thread(target=lambda: world.send(xh, 3, tag=2,
                                                         rank=1))]
        for t in ts: t.start()
        for t in ts: t.join()
        wall = time.perf_counter() - t0
    elif me == 1:
        world.recv(source=1, tag=2, rank=3)
        world.recv(source=0, tag=1, rank=3)
    world.barrier()
    if me == 0:
        lines.append({
            "metric": "wire_hol_2x%%dMiB_lanes%%d" %% (HOL_MIB, lanes),
            "value": round(_hol() - h0, 4), "unit": "hol_wait_s",
            "vs_baseline": None, "suite": "wire",
            "wall_s": round(wall, 4),
        })
mca_var.VARS.unset("wire_p2p_lanes")

# -- spanning-comm allgatherv round: three wire configurations -------------
#   pipelined     zero-copy fragments + overlapped reap (the PR path)
#   legacy_frames wire_pipeline_segsize=0 (tobytes + ordered join)
#   sequential    pipelined frames, fixed process-order reap
agv = np.arange((AGV_MIB << 20) // 4, dtype=np.float32)
bufs = [agv + r for r in world.local_comm_ranks]
configs = (("pipelined", 1 << 20, True),
           ("legacy_frames", 0, True),
           ("sequential", 1 << 20, False))
times = {}
for key, seg, overlap in configs:
    mca_var.set_value("wire_pipeline_segsize", seg)
    mca_var.set_value("wire_overlap_exchange", overlap)
    world.barrier()
    best = None
    for _ in range(3):
        world.barrier()
        t0 = time.perf_counter()
        out = world.allgatherv(bufs)
        dt = time.perf_counter() - t0
        assert np.asarray(out).shape[0] == world.size * agv.shape[0]
        best = dt if best is None else min(best, dt)
    times[key] = best
mca_var.VARS.unset("wire_pipeline_segsize")
mca_var.VARS.unset("wire_overlap_exchange")

# -- skewed exchange: time-to-first-data, arrival order vs process order ---
# Process 1 (FIRST in reap order) enters its round late; the overlap
# reap returns process 2's payload almost immediately while the
# sequential baseline parks on the slow peer — the latency a pipelined
# consumer of early rows actually feels.
SKEW_S = 0.4
first = {}
rt_router = rt.wire
for key, overlap in (("overlap", True), ("sequential", False)):
    world.barrier()
    if me == 0:
        t0 = time.perf_counter()
        if overlap:
            pending = {1: 1, 2: 1}
            src, _arr = rt_router.coll_recv_any(world, pending)
            first[key] = time.perf_counter() - t0
            pending[src] -= 1
            while sum(pending.values()):
                s2, _ = rt_router.coll_recv_any(world, pending)
                pending[s2] -= 1
        else:
            _ = rt_router.coll_recv(world, 1)   # parks on the slow peer
            first[key] = time.perf_counter() - t0
            _ = rt_router.coll_recv(world, 2)
    elif me == 1:
        time.sleep(SKEW_S)
        rt_router.coll_send(world, 0, agv)
    else:
        rt_router.coll_send(world, 0, agv)
    world.barrier()

if me == 0:
    for key, _seg, _ov in configs:
        lines.append({
            "metric": "wire_allgatherv_%%dMiB_%%s" %% (AGV_MIB, key),
            "value": round(times[key], 4), "unit": "s",
            "vs_baseline": None, "suite": "wire",
        })
    lines.append({
        "metric": "wire_allgatherv_pipeline_speedup",
        "value": round(times["legacy_frames"]
                       / max(times["pipelined"], 1e-9), 4),
        "unit": "x_vs_legacy_framing", "vs_baseline": None,
        "suite": "wire",
    })
    lines.append({
        "metric": "wire_allgatherv_overlap_speedup",
        "value": round(times["sequential"]
                       / max(times["pipelined"], 1e-9), 4),
        "unit": "x_vs_sequential", "vs_baseline": None, "suite": "wire",
    })
    lines.append({
        "metric": "wire_skewed_first_data_overlap",
        "value": round(first["overlap"], 4), "unit": "s",
        "vs_baseline": None, "suite": "wire",
        "sequential_s": round(first["sequential"], 4),
        "first_data_speedup": round(
            first["sequential"] / max(first["overlap"], 1e-9), 2),
        "skew_s": SKEW_S,
        "pvars": {k: v for k, v in pvar.PVARS.read_all().items()
                  if k.startswith(("wire_", "btl_dcn_"))},
        "cumulative": True,
    })
    with open(os.environ["OMPITPU_WIRE_BENCH_OUT"], "w") as f:
        json.dump(lines, f)
world.barrier()
mpi.finalize()
'''


#: worker app for the hier_scaling micro-suite: a REAL 4-process
#: tpurun job (one device per process) timing the spanning-collective
#: INTER schedules against each other and reading the per-process
#: hier_inter_bytes / hier_inter_msgs_sent deltas that prove the
#: O(P^2) -> O(log P) / ~2n claims. Process 0 writes the JSON lines to
#: OMPITPU_HIER_BENCH_OUT.
_HIER_BENCH_APP = r'''
import json, math, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var

SIZE = int(os.environ.get("OMPITPU_HIER_BENCH_BYTES", str(256 << 10)))
world = mpi.init()
from ompi_release_tpu.runtime.runtime import Runtime
rt = Runtime.current()
me = rt.bootstrap["process_index"]
P = n_procs = 4
assert world.size == 4, world.size

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

x = np.ones((1, SIZE // 4), np.float32) * (me + 1)
want = float(sum(r + 1 for r in range(world.size)))
ALGS = ("linear", "recursive_doubling", "ring", "rabenseifner")
deltas, times = [], []
for alg in ALGS:
    mca_var.set_value("hier_inter_algorithm", alg)
    world.barrier()
    world.allreduce(x)          # warm the schedule + shadow programs
    world.barrier()
    b0 = _pv("hier_inter_bytes")
    t0 = time.perf_counter()
    got = np.asarray(world.allreduce(x))
    dt = time.perf_counter() - t0
    deltas.append(_pv("hier_inter_bytes") - b0)
    times.append(dt)
    assert abs(float(got[0][0]) - want) < 1e-3, got[0][0]
    mca_var.VARS.unset("hier_inter_algorithm")

# bcast: root send count, linear P-1 vs binomial ceil(log2 P)
bd = {}
for alg in ("linear", "binomial"):
    mca_var.set_value("hier_inter_algorithm", alg)
    world.barrier()
    s0 = _pv("hier_inter_msgs_sent")
    world.bcast(x, root=0)
    bd[alg] = _pv("hier_inter_msgs_sent") - s0
    mca_var.VARS.unset("hier_inter_algorithm")
world.barrier()

# every process's byte deltas to process 0 (AFTER the measurements)
rows = world.gatherv([np.asarray(deltas, np.float32)], root=0)
if me == 0:
    per_proc = np.asarray(rows).reshape(world.size, len(ALGS))
    lines = []
    for i, alg in enumerate(ALGS):
        worst = float(per_proc[:, i].max())
        lines.append({
            "metric": "hier_allreduce_%%dKiB_inter_bytes_%%s"
                      %% (SIZE >> 10, alg),
            "value": round(worst / SIZE, 4),
            "unit": "xN_bytes_per_proc_max", "vs_baseline": None,
            "suite": "hier_scaling", "procs": world.size,
            "per_proc_xN": [round(float(v) / SIZE, 4)
                            for v in per_proc[:, i]],
            "seconds": round(times[i], 5),
        })
    lines.append({
        "metric": "hier_bcast_root_msgs",
        "value": bd["binomial"], "unit": "sends_at_root",
        "vs_baseline": None, "suite": "hier_scaling",
        "linear_sends": bd["linear"],
        "binomial_depth_bound": math.ceil(math.log2(world.size)),
        "pvars": {k: v for k, v in pvar.PVARS.read_all().items()
                  if k.startswith("hier_")},
        "cumulative": True,
    })
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f)
world.barrier()
mpi.finalize()
'''


def _hier_micro_suite():
    """hier_scaling lines: per-process inter BYTES of a 4-process
    spanning allreduce under every schedule (linear's (P-1)n = 3n vs
    ring/Rabenseifner's <= 2n + padding), and the bcast root's send
    count dropping from P-1 to the binomial ceil(log2 P) — measured
    through a real 4-process tpurun job on the CPU mesh (the inter
    step rides host wire transports either way)."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    lines = run_loopback_app(
        4, _HIER_BENCH_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__))},
        {"OMPITPU_HIER_BENCH_BYTES": str(
            1 << 20)},
        "hier_bench.json", timeout_s=300)
    if lines is None:
        return [{"metric": "hier_scaling_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "hier bench job failed"}]
    return lines  # main()'s emit() stamps the tier label


def _wire_micro_suite():
    """Cross-process wire lines: p2p ping-pong bandwidth (1 MiB up to
    256 MiB on full machines), two concurrent distinct-tag transfers
    under 4 lanes vs 1 (the head-of-line pvar is the metric), and a
    spanning-comm allgatherv with overlapped vs sequential reaping —
    all through a REAL 3-process tpurun job, CPU mesh (the wire rides
    host sockets/shm either way)."""
    import os
    import sys as _sys
    import tempfile

    from ompi_release_tpu.tools.tpurun import Job

    sizes = [1 << 20, 16 << 20, 64 << 20, 256 << 20]
    with tempfile.TemporaryDirectory() as td:
        app = os.path.join(td, "wire_bench_app.py")
        out_path = os.path.join(td, "wire_bench.json")
        with open(app, "w") as f:
            f.write(_WIRE_BENCH_APP % {"repo": os.path.dirname(
                os.path.abspath(__file__))})
        env_keep = dict(os.environ)
        os.environ["OMPITPU_WIRE_BENCH_SIZES"] = json.dumps(sizes)
        os.environ["OMPITPU_WIRE_BENCH_OUT"] = out_path
        os.environ["OMPITPU_WIRE_BENCH_HOL_MIB"] = "32"
        os.environ["OMPITPU_WIRE_BENCH_AGV_MIB"] = "4"
        try:
            job = Job(3, [_sys.executable, app], [], heartbeat_s=0.5,
                      miss_limit=8)
            rc = job.run(timeout_s=420)
        finally:
            os.environ.clear()
            os.environ.update(env_keep)
        if rc != 0 or not os.path.exists(out_path):
            return [{"metric": "wire_micro_suite", "value": None,
                     "unit": None, "vs_baseline": None,
                     "error": f"wire bench job rc={rc}"}]
        with open(out_path) as f:
            lines = json.load(f)
    return lines  # main()'s emit() stamps the tier label


#: worker app for the native_wire micro-suite: 2-process tpurun jobs
#: on the CPU mesh driving the SAME p2p ping-pong through three byte
#: paths — the shm ring (co-hosted, the headline numbers), the
#: vectored socket (forced cross-host via OMPITPU_HOST_ID), and the
#: portable staged frames (capability cards stripped LIVE mid-job,
#: proving the per-peer fallback reassembles the byte-identical
#: framing) — plus HOL-lane and QoS legs over the native BTL and the
#: wire_native_copies_per_mib zero-copy witness. Process 0 writes its
#: JSON lines to OMPITPU_LOOPBACK_OUT.
_NATIVE_WIRE_BENCH_APP = r'''
import json, os, sys, threading, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
MODE = os.environ["OMPITPU_NW_BENCH_MODE"]  # shm | tcp | qos
if MODE == "tcp":
    # distinct shm identity: fragments ride the vectored socket path
    os.environ["OMPITPU_HOST_ID"] = (
        "nwbench-" + os.environ["OMPITPU_NODE_ID"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime.runtime import Runtime

if MODE == "qos":
    # QoS lane partitioning must exist before the router comes up
    mca_var.set_value("wire_qos_classes", "latency:3,bulk:1")
    mca_var.set_value("wire_qos_class", "latency")
SIZES = json.loads(os.environ.get("OMPITPU_NW_BENCH_SIZES", "[]"))
HOL_MIB = int(os.environ.get("OMPITPU_NW_BENCH_HOL_MIB", "8"))
world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
peer = 1 - me
assert rt.wire._nw is not None, "native datapath did not come up"
assert rt.wire._btl_for(peer).NAME == "nativewire"
lines = []

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

def pingpong_rtt(size, tag):
    """Best-of-3 round trip of `size` bytes each way; seconds."""
    x = np.ones(max(1, size // 4), np.float32)
    best = None
    for _ in range(3):
        world.barrier()
        if me == 0:
            t0 = time.perf_counter()
            world.send(x, 2, tag=tag, rank=0)
            v, _st = world.recv(source=2, tag=tag + 1, rank=0)
            dt = time.perf_counter() - t0
            assert np.asarray(v).shape == x.shape
            best = dt if best is None else min(best, dt)
        else:
            v, _st = world.recv(source=0, tag=tag, rank=2)
            world.send(np.asarray(v), 0, tag=tag + 1, rank=2)
    return best

if MODE in ("shm", "tcp"):
    suffix = "" if MODE == "shm" else "tcp_"
    for size in SIZES:
        rtt = pingpong_rtt(size, 11)
        if me == 0:
            lines.append({
                "metric": "wire_native_p2p_%%s%%dMiB" %% (suffix,
                                                          size >> 20),
                "value": round(2 * size / rtt / 1e9, 4), "unit": "GB/s",
                "vs_baseline": None, "suite": "native_wire",
                "rtt_s": round(rtt, 5)})

if MODE == "tcp":
    # live per-peer fallback: strip the capability cards and the SAME
    # transfers ride the portable staged frames — receivers that race
    # the strip still reassemble (the framing is byte-identical)
    for c in rt.bootstrap["peer_cards"]:
        if isinstance(c, dict):
            c.pop("nativewire", None)
    world.barrier()
    assert rt.wire._btl_for(peer).NAME == "dcn"
    for size in SIZES:
        rtt = pingpong_rtt(size, 31)
        if me == 0:
            lines.append({
                "metric": "wire_staged_p2p_%%dMiB" %% (size >> 20),
                "value": round(2 * size / rtt / 1e9, 4), "unit": "GB/s",
                "vs_baseline": None, "suite": "native_wire",
                "rtt_s": round(rtt, 5)})

if MODE == "shm":
    # HOL leg: two concurrent distinct-tag transfers over the native
    # rings, 4 lanes vs 1 — the head-of-line pvar is the metric,
    # mirroring the portable wire suite's leg on the native BTL
    xh = np.ones((HOL_MIB << 20) // 4, np.float32)
    for lanes in (4, 1):
        mca_var.set_value("wire_p2p_lanes", lanes)
        world.barrier()
        h0 = _pv("wire_hol_wait_seconds")
        if me == 0:
            ts = [threading.Thread(target=lambda t=t: world.send(
                      xh, 2, tag=t, rank=0)) for t in (51, 52)]
            for t in ts: t.start()
            for t in ts: t.join()
        else:
            world.recv(source=0, tag=52, rank=2)
            world.recv(source=0, tag=51, rank=2)
        world.barrier()
        if me == 0:
            lines.append({
                "metric": "wire_native_hol_2x%%dMiB_lanes%%d"
                          %% (HOL_MIB, lanes),
                "value": round(_pv("wire_hol_wait_seconds") - h0, 4),
                "unit": "hol_wait_s", "vs_baseline": None,
                "suite": "native_wire"})
    mca_var.VARS.unset("wire_p2p_lanes")
    if me == 0:
        lines.append({
            "metric": "wire_native_copies_per_mib",
            "value": round(_pv("wire_native_copies_per_mib"), 5),
            "unit": "copies/MiB", "vs_baseline": None,
            "suite": "native_wire",
            "native_bytes": _pv("wire_native_bytes"),
            "native_frames": _pv("wire_native_frames"),
            "fallback_copies": _pv("wire_native_fallback_copies")})

if MODE == "qos":
    # QoS leg on the native BTL: with the lane space partitioned by
    # class, a small latency-probe pingpong is timed solo and then
    # under a concurrent 6 x 16 MiB bulk stream on its own tag
    def lat_round(tag, reps):
        xs = np.ones((64 << 10) // 4, np.float32)
        ts = []
        for _i in range(reps):
            if me == 0:
                t0 = time.perf_counter()
                world.send(xs, 2, tag=tag, rank=0)
                world.recv(source=2, tag=tag + 1, rank=0)
                ts.append(time.perf_counter() - t0)
            else:
                world.recv(source=0, tag=tag, rank=2)
                world.send(xs, 0, tag=tag + 1, rank=2)
        return ts

    world.barrier()
    solo = lat_round(81, 10)
    world.barrier()
    xb = np.ones((16 << 20) // 4, np.float32)

    def _bulk():
        # its own rank pair (1 -> 3): the latency probe's 0 <-> 2
        # envelopes never share a queue with the bulk stream
        for _k in range(6):
            if me == 0:
                world.send(xb, 3, tag=71, rank=1)
            else:
                world.recv(source=1, tag=71, rank=3)

    th = threading.Thread(target=_bulk)
    th.start()
    under = lat_round(91, 10)
    th.join(timeout=180)
    assert not th.is_alive(), "bulk stream wedged"
    world.barrier()
    if me == 0:
        lines.append({
            "metric": "wire_native_qos_latency_solo_s",
            "value": round(sum(solo) / len(solo), 6), "unit": "s",
            "vs_baseline": None, "suite": "native_wire",
            "qos_classes": "latency:3,bulk:1"})
        lines.append({
            "metric": "wire_native_qos_latency_under_bulk_s",
            "value": round(sum(under) / len(under), 6), "unit": "s",
            "vs_baseline": None, "suite": "native_wire",
            "qos_classes": "latency:3,bulk:1"})

if me == 0:
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f)
world.barrier()
mpi.finalize()
'''


def _native_wire_micro_suite():
    """native_wire lines: the zero-copy datapath's p2p ping-pong
    through all three byte paths (native shm ring / native vectored
    socket / portable staged frames via a LIVE per-peer capability
    strip), the headline ``wire_native_p2p_256MiB`` GB/s line on full
    machines, the ``wire_native_copies_per_mib`` zero-copy witness,
    HOL-lane and QoS legs over the native BTL, and the derived
    ``wire_native_shm_speedup_vs_staged`` acceptance factor. Withdraws
    with an informational line when the native symbols are absent —
    the portable-only build is a supported configuration, not a bench
    failure."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    try:
        from ompi_release_tpu.native import wire_symbols_available
        have = bool(wire_symbols_available())
    except Exception:
        have = False
    if not have:
        return [{"metric": "native_wire_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "native wire symbols unavailable "
                          "(portable staged path in force)"}]
    sizes = [1 << 20, 16 << 20, 64 << 20, 256 << 20]
    repo = os.path.dirname(os.path.abspath(__file__))
    app = _NATIVE_WIRE_BENCH_APP % {"repo": repo}
    lines = []
    for mode, timeout in (("shm", 420), ("tcp", 420), ("qos", 240)):
        got = run_loopback_app(
            2, app,
            {"OMPITPU_NW_BENCH_MODE": mode,
             "OMPITPU_NW_BENCH_SIZES": json.dumps(sizes),
             "OMPITPU_NW_BENCH_HOL_MIB": "32"},
            "native_wire_%s.json" % mode, timeout_s=timeout)
        if got is None:
            lines.append({"metric": "native_wire_%s_leg" % mode,
                          "value": None, "unit": None,
                          "vs_baseline": None,
                          "error": "native wire bench job failed"})
            continue
        lines.extend(got)
    by = {ln["metric"]: ln for ln in lines
          if ln.get("value") is not None}
    top = sizes[-1] >> 20
    nat = by.get("wire_native_p2p_%dMiB" % top)
    stg = by.get("wire_staged_p2p_%dMiB" % top)
    if nat and stg and stg["value"]:
        lines.append({
            "metric": "wire_native_shm_speedup_vs_staged",
            "value": round(nat["value"] / stg["value"], 4),
            "unit": "x_vs_staged", "vs_baseline": None,
            "suite": "native_wire", "size_mib": top})
    return lines


#: worker app for the native_obs micro-suite: the SAME shm-ring p2p
#: loop with the always-on C counter blocks (every build has them),
#: once with the optional native event ring OFF (the baseline wall)
#: and once ON (one 32-byte C-side record per fragment) — the wall
#: ratio is the observability plane's cost on the zero-copy byte
#: path. A third 3-proc mode sends a ring of transfers with the event
#: ring AND obs dumps on, so the parent can doctor-merge the
#: nativeev-p*.json dumps and count reconstructed cross-process
#: fragment flows. Process 0 writes JSON lines to
#: OMPITPU_LOOPBACK_OUT.
_NATIVE_OBS_BENCH_APP = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
MODE = os.environ["OMPITPU_NOBS_MODE"]  # counters | events | doctor
SIZE = int(os.environ.get("OMPITPU_NOBS_SIZE", str(2 << 20)))
REPS = int(os.environ.get("OMPITPU_NOBS_REPS", "10"))
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.obs import nativeev as obs_nativeev
from ompi_release_tpu.runtime.runtime import Runtime

world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
assert rt.wire._nw is not None, "native datapath did not come up"
# the event ring must track its cvar: ON only in the events/doctor legs
assert (obs_nativeev.get_ring() is not None) == (MODE != "counters"), (
    "event-ring lifecycle does not match btl_nativewire_events")
lines = []

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

if MODE in ("counters", "events"):
    x = np.ones(max(1, SIZE // 4), np.float32)

    def _round(reps):
        t0 = time.perf_counter()
        for _i in range(reps):
            if me == 0:
                world.send(x, 2, tag=13, rank=0)
                v, _st = world.recv(source=2, tag=14, rank=0)
            else:
                v, _st = world.recv(source=0, tag=13, rank=2)
                world.send(np.asarray(v), 0, tag=14, rank=2)
        return time.perf_counter() - t0

    world.barrier()
    _round(1)  # warmup: ring attach + first-touch stay out of walls
    wall = None
    for _b in range(3):
        world.barrier()
        dt = _round(REPS)
        wall = dt if wall is None else min(wall, dt)
    world.barrier()
    if me == 0:
        lines.append({
            "metric": "native_obs_%%s_wall_s" %% MODE,
            "value": round(wall, 5), "unit": "s",
            "vs_baseline": None, "suite": "native_obs",
            "reps": REPS, "size_mib": SIZE >> 20,
            "native_bytes": _pv("wire_native_bytes")})
        if MODE == "counters":
            # the C counter blocks themselves, as gate-tracked lines
            lines.append({
                "metric": "wire_native_stall_count",
                "value": _pv("wire_native_ring_stalls"),
                "unit": "stalls", "vs_baseline": None,
                "suite": "native_obs"})
            lines.append({
                "metric": "wire_native_stall_seconds",
                "value": round(_pv("wire_native_stall_seconds"), 5),
                "unit": "s", "vs_baseline": None,
                "suite": "native_obs"})
            lines.append({
                "metric": "wire_native_ring_hwm_frac",
                "value": round(_pv("wire_native_ring_hwm_frac"), 5),
                "unit": "frac", "vs_baseline": None,
                "suite": "native_obs"})
        else:
            lines.append({
                "metric": "native_obs_event_records",
                "value": float(obs_nativeev.get_ring().count()),
                "unit": None, "vs_baseline": None,
                "suite": "native_obs"})

if MODE == "doctor":
    # ring of staged transfers: proc i's rank 2i -> proc (i+1)%%3's
    # rank (2i+2)%%6, sequential with barriers (no deadlock to manage)
    x = np.ones(max(1, SIZE // 4), np.float32)
    hops = ((0, 1, 0, 2), (1, 2, 2, 4), (2, 0, 4, 0))
    for tag_off, (src, dst, srank, drank) in enumerate(hops):
        world.barrier()
        if me == src:
            world.send(x, drank, tag=41 + tag_off, rank=srank)
        elif me == dst:
            v, _st = world.recv(source=srank, tag=41 + tag_off,
                                rank=drank)
            assert np.asarray(v).shape == x.shape
    world.barrier()
    if me == 0:
        lines.append({"metric": "native_obs_doctor_leg_ok",
                      "value": 1.0, "unit": None,
                      "vs_baseline": None, "suite": "native_obs"})

if me == 0:
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f)
world.barrier()
mpi.finalize()
'''


def _native_obs_micro_suite():
    """native_obs lines: the native-wire observability plane's cost
    and fidelity. ``native_obs_counters_wall_s`` is the p2p wall with
    ONLY the always-on C counter blocks (every build pays this — the
    gate trends it across rounds); ``native_obs_events_wall_s`` adds
    the optional event ring (one 32-byte C record per fragment), and
    ``native_obs_overhead_ratio`` is events/counters with the 1.05
    acceptance budget. The doctor leg runs a 3-process job with the
    event ring and obs dumps on, doctor-merges the ``nativeev-p*``
    dumps, and reports how many cross-process native fragment flows
    reconstructed with paired ids. Withdraws with an informational
    line when the native telemetry symbols are absent."""
    import os
    import tempfile

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    try:
        from ompi_release_tpu.native import (
            telemetry_symbols_available, wire_symbols_available)
        have = bool(wire_symbols_available()
                    and telemetry_symbols_available())
    except Exception:
        have = False
    if not have:
        return [{"metric": "native_obs_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "native telemetry symbols unavailable "
                          "(stale .so or portable-only build)"}]
    size = 8 << 20
    reps = 40
    repo = os.path.dirname(os.path.abspath(__file__))
    app = _NATIVE_OBS_BENCH_APP % {"repo": repo}
    lines = []
    walls = {}
    for mode in ("counters", "events"):
        mca = ([("btl_nativewire_events", "1")]
               if mode == "events" else [])
        got = run_loopback_app(
            2, app,
            {"OMPITPU_NOBS_MODE": mode,
             "OMPITPU_NOBS_SIZE": str(size),
             "OMPITPU_NOBS_REPS": str(reps)},
            "native_obs_%s.json" % mode, timeout_s=300, mca=mca)
        if got is None:
            lines.append({"metric": "native_obs_%s_leg" % mode,
                          "value": None, "unit": None,
                          "vs_baseline": None,
                          "error": "native obs bench job failed"})
            continue
        lines.extend(got)
        for ln in got:
            if ln.get("metric") == "native_obs_%s_wall_s" % mode:
                walls[mode] = ln.get("value")
    if walls.get("counters") and walls.get("events"):
        lines.append({
            "metric": "native_obs_overhead_ratio",
            "value": round(walls["events"] / walls["counters"], 4),
            "unit": "ratio", "vs_baseline": None,
            "suite": "native_obs", "budget": 1.05})
    # doctor-merge fidelity: 3 processes, event ring + obs dumps on
    with tempfile.TemporaryDirectory() as dump_dir:
        got = run_loopback_app(
            3, app,
            {"OMPITPU_NOBS_MODE": "doctor",
             "OMPITPU_NOBS_SIZE": str(1 << 20),
             "OMPITPU_NOBS_REPS": "1"},
            "native_obs_doctor.json", timeout_s=300,
            mca=[("btl_nativewire_events", "1"),
                 ("obs_enable", "1"),
                 ("obs_dump_dir", dump_dir)])
        if got is None:
            lines.append({"metric": "native_obs_doctor_leg",
                          "value": None, "unit": None,
                          "vs_baseline": None,
                          "error": "native obs doctor job failed"})
        else:
            from ompi_release_tpu.obs import doctor as _doctor

            dumps = _doctor.load_dir(dump_dir)
            nw = [s for d in dumps for s in d.get("spans", ())
                  if s.get("nativeev")]
            pairs = [p for p in _doctor.flow_pairs(dumps)
                     if p["cross_process"]
                     and p["src"].get("nativeev")]
            lines.append({
                "metric": "native_obs_doctor_nativeev_spans",
                "value": float(len(nw)), "unit": None,
                "vs_baseline": None, "suite": "native_obs",
                "procs": len(dumps)})
            lines.append({
                "metric": "native_obs_doctor_flow_pairs",
                "value": float(len(pairs)), "unit": None,
                "vs_baseline": None, "suite": "native_obs"})
    return lines


#: worker app for the overlap micro-suite: a REAL 3-process tpurun job
#: measuring exposed vs hidden comm time — blocking allreduce-per-
#: bucket followed by compute, vs overlapped iallreduce buckets
#: (parallel/dp.GradientSync) issued UNDER the compute loop — once
#: with the async progress engine's thread enabled and once in the
#: polling fallback. Process 0 writes its JSON lines to
#: OMPITPU_LOOPBACK_OUT.
_OVERLAP_BENCH_APP = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# distinct shm identity per worker: comm rides the DCN staged path so
# the hidden/exposed split measures real wire time, not a memcpy
os.environ["OMPITPU_HOST_ID"] = (
    "ovlbench-" + os.environ["OMPITPU_NODE_ID"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.parallel.dp import GradientSync
from ompi_release_tpu.runtime.runtime import Runtime

LEAF = int(os.environ.get("OMPITPU_OVERLAP_LEAF", "48000"))
world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
ln = len(world.local_comm_ranks)
grads = {"w%%d" %% k: np.ones((ln, LEAF), np.float32) * (me + k + 1)
         for k in range(6)}
sync = GradientSync(world, mean=False, bucket_bytes=1 << 20)

def _pv(name):
    p = pvar.PVARS.lookup(name)
    v = p.read() if p is not None else 0.0
    return float(v) if not isinstance(v, dict) else 0.0

def blocking_step():
    for k in sorted(grads):
        world.allreduce(grads[k])

def compute(seconds):
    a = np.ones((96, 96), np.float32)
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        a = a @ a * 1e-4

# warm every compiled program / wire channel once
blocking_step()
sync.issue(grads).wait()

# comm time alone: the blocking allreduce-per-bucket cost per step
world.barrier()
best = None
for _ in range(3):
    world.barrier()
    t0 = time.perf_counter()
    blocking_step()
    dt = time.perf_counter() - t0
    best = dt if best is None else min(best, dt)
t_comm = best
t_compute = max(t_comm, 0.02)

results = {}
for mode in ("engine", "polling"):
    if mode == "engine":
        mca_var.set_value("progress_thread", True)
    else:
        mca_var.VARS.unset("progress_thread")
    world.barrier()
    t_block = t_ovl = None
    for _ in range(3):
        world.barrier()
        t0 = time.perf_counter()
        blocking_step()
        compute(t_compute)
        dt = time.perf_counter() - t0
        t_block = dt if t_block is None else min(t_block, dt)
        world.barrier()
        h0 = _pv("nbc_hidden_seconds")
        t0 = time.perf_counter()
        pending = sync.issue(grads)
        compute(t_compute)
        out = pending.wait()
        dt = time.perf_counter() - t0
        t_ovl = dt if t_ovl is None else min(t_ovl, dt)
    # parity witness: the overlapped result equals the blocking one
    ref = np.asarray(world.allreduce(grads["w0"]))
    np.testing.assert_allclose(np.asarray(out["w0"]), ref, rtol=1e-6)
    hidden_s = _pv("nbc_hidden_seconds") - h0
    results[mode] = {
        "t_block": t_block, "t_ovl": t_ovl,
        # the gated value is the ENGINE'S OWN accounting of comm time
        # that ran while the caller computed (the nbc_hidden_seconds
        # pvar over the last overlapped step, against the measured
        # comm-alone time): engine leg ~1, polling leg exactly 0. The
        # wall-clock fraction rides along as a label — it also absorbs
        # cross-process skew, so it is noisier than the pvar witness.
        "hidden_frac": max(0.0, min(1.0, hidden_s / max(t_comm, 1e-9))),
        "wall_hidden_frac": max(0.0, min(1.0, (t_block - t_ovl)
                                         / max(t_comm, 1e-9))),
        "hidden_pvar_s": hidden_s,
    }
mca_var.VARS.unset("progress_thread")

if me == 0:
    lines = []
    for mode, r in results.items():
        suffix = "" if mode == "engine" else "_polling"
        lines.append({
            "metric": "overlap_allreduce_hidden_frac" + suffix,
            "value": round(r["hidden_frac"], 4), "unit": "frac_hidden",
            "vs_baseline": None, "suite": "overlap",
            "t_block_s": round(r["t_block"], 5),
            "t_overlap_s": round(r["t_ovl"], 5),
            "t_comm_s": round(t_comm, 5),
            "wall_hidden_frac": round(r["wall_hidden_frac"], 4),
            "nbc_hidden_delta_s": round(r["hidden_pvar_s"], 5),
        })
    lines.append({
        "metric": "overlap_allreduce_speedup",
        "value": round(results["engine"]["t_block"]
                       / max(results["engine"]["t_ovl"], 1e-9), 4),
        "unit": "x_vs_blocking", "vs_baseline": None,
        "suite": "overlap",
        "pvars": {k: v for k, v in pvar.PVARS.read_all().items()
                  if k.startswith(("nbc_", "progress_",
                                   "wire_coll_pumped"))},
        "cumulative": True,
    })
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f)
world.barrier()
mpi.finalize()
'''


def _overlap_micro_suite():
    """overlap lines: exposed vs hidden comm time for gradient-bucket
    allreduce through a REAL 3-process tpurun job, CPU mesh (the wire
    and the progress engine are host-side either way). The engine leg
    runs with the dedicated progress thread (hidden fraction > 0 —
    comm rode under the compute loop); the polling leg is the
    deterministic fallback where schedules drain at wait() (hidden
    fraction ~0). Gate direction: frac_hidden / overlap_* are
    higher-better."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    lines = run_loopback_app(
        3, _OVERLAP_BENCH_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__))},
        {"OMPITPU_OVERLAP_LEAF": "96000"},
        "overlap_bench.json", timeout_s=300)
    if lines is None:
        return [{"metric": "overlap_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "overlap bench job failed"}]
    return lines  # main()'s emit() stamps the tier label


#: worker app for the tree_overlap micro-suite: a REAL 3-process
#: tpurun job training a tiny models/transformer.TpuLM locally per
#: process (the data-parallel trainer shape) and syncing the WHOLE
#: gradient pytree through parallel/tree.TreeSync — per-leaf blocking
#: allreduces vs one planned fused pass overlapped under the next
#: step's real fwd/bwd, engine vs polling legs; plus a HostPipeline
#: microbatch leg with blocking vs nonblocking stage-boundary
#: transfers. Process 0 writes the JSON lines to OMPITPU_LOOPBACK_OUT.
_TREE_BENCH_APP = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# distinct shm identity per worker: comm rides the DCN staged path so
# hidden/exposed splits measure real wire time, not a memcpy
os.environ["OMPITPU_HOST_ID"] = (
    "treebench-" + os.environ["OMPITPU_NODE_ID"])
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import ompi_release_tpu as mpi
from jax.sharding import Mesh
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.models import transformer as tfm
from ompi_release_tpu.parallel import pp as pp_mod, tree as tree_mod
from ompi_release_tpu.runtime.runtime import Runtime

world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]

def _pv(name):
    p = pvar.PVARS.lookup(name)
    v = p.read() if p is not None else 0.0
    return float(v) if not isinstance(v, dict) else 0.0

# ---- the trainer: a tiny TpuLM on this process's 1-device mesh ------
cfg = tfm.ModelConfig(vocab=128, d_model=64, n_layers=2, n_heads=2,
                      head_dim=16, d_ff=192, max_seq=32,
                      microbatches=1, dtype=jnp.float32)
mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
            ("dp", "pp", "sp", "ep", "tp"))
params = tfm.init_params(jax.random.PRNGKey(0), cfg)
loss_fn = tfm.make_forward(cfg, mesh)
grad_fn = jax.jit(jax.value_and_grad(loss_fn))
rng = np.random.RandomState(me)
toks = rng.randint(0, cfg.vocab, (4, 32)).astype(np.int32)
tgts = rng.randint(0, cfg.vocab, (4, 32)).astype(np.int32)

def grad_step():
    _, g = grad_fn(params, toks, tgts)
    return jax.block_until_ready(g)

grads = grad_step()  # compile + first real backward
t0 = time.perf_counter()
grad_step()
t_grad = time.perf_counter() - t0
# driver-mode tree: leading member-slice axis on every leaf
gtree = jax.tree.map(lambda g: np.asarray(g)[None], grads)
leaves = jax.tree.leaves(gtree)
tree_bytes = sum(l.nbytes for l in leaves)

def blocking_perleaf():
    for l in leaves:
        world.allreduce(l)

sync = tree_mod.TreeSync(world, mean=False, bucket_bytes=1 << 20)
blocking_perleaf()          # warm per-leaf programs/channels
sync.issue(gtree).wait()    # warm the planned pass + plan cache

# comm time alone, both shapes
world.barrier()
t_perleaf = t_planned = None
for _ in range(3):
    world.barrier()
    t0 = time.perf_counter()
    blocking_perleaf()
    dt = time.perf_counter() - t0
    t_perleaf = dt if t_perleaf is None else min(t_perleaf, dt)
    world.barrier()
    t0 = time.perf_counter()
    sync.issue(gtree).wait()
    dt = time.perf_counter() - t0
    t_planned = dt if t_planned is None else min(t_planned, dt)

def compute(seconds):
    # REAL trainer compute: fwd/bwd steps until the budget elapses
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        grad_step()

t_compute = max(t_planned, t_grad, 0.02)
results = {}
for mode in ("engine", "polling"):
    if mode == "engine":
        mca_var.set_value("progress_thread", True)
    else:
        mca_var.VARS.unset("progress_thread")
    world.barrier()
    t_block = t_ovl = None
    for _ in range(3):
        world.barrier()
        t0 = time.perf_counter()
        blocking_perleaf()
        compute(t_compute)
        dt = time.perf_counter() - t0
        t_block = dt if t_block is None else min(t_block, dt)
        world.barrier()
        h0 = _pv("nbc_hidden_seconds")
        th0 = _pv("tree_hidden_seconds")
        t0 = time.perf_counter()
        pending = sync.issue(gtree)
        compute(t_compute)
        out = pending.wait()
        dt = time.perf_counter() - t0
        t_ovl = dt if t_ovl is None else min(t_ovl, dt)
    # parity witness: planned overlapped pass == per-leaf blocking
    ref = np.asarray(world.allreduce(
        np.asarray(grads["embed"])[None]))
    np.testing.assert_array_equal(np.asarray(out["embed"]), ref)
    hidden_s = _pv("nbc_hidden_seconds") - h0
    results[mode] = {
        "t_block": t_block, "t_ovl": t_ovl,
        # the gated witness: the ENGINE'S own accounting of comm time
        # that ran under the trainer's fwd/bwd (nbc_hidden_seconds
        # delta over the last overlapped pass vs the measured planned
        # comm-alone time); engine ~1, polling exactly 0
        "hidden_frac": max(0.0, min(1.0,
                                    hidden_s / max(t_planned, 1e-9))),
        "tree_hidden_s": _pv("tree_hidden_seconds") - th0,
        "nbc_hidden_s": hidden_s,
    }
mca_var.VARS.unset("progress_thread")

# ---- HostPipeline: microbatch schedule, boundary comm nb vs blocking
# 512 KiB boundary activations (the trainer-scale shape where the
# transfer is worth hiding) under the progress thread, so posted-early
# irecvs/isends complete off the caller while the stage computes
S = world.size
m = 6
W = rng.randn(512, 512).astype(np.float32) * 0.05
mbs = [np.ones((256, 512), np.float32) * (k + 1) for k in range(m)]

def stage_fn(x):
    y = np.asarray(x)
    for _ in range(3):  # one stage's compute per microbatch
        y = np.tanh(y @ W)
    return y

mca_var.set_value("progress_thread", True)
pp_res = {}
for leg, nb in (("nonblocking", True), ("blocking", False)):
    pipe = pp_mod.HostPipeline(world, stage_fn, stage=me,
                               nonblocking=nb)
    world.barrier()
    pipe.run(mbs)  # warm channels
    best = None
    w0 = _pv("pp_boundary_wait_seconds")
    for _ in range(3):
        world.barrier()
        t0 = time.perf_counter()
        outs = pipe.run(mbs)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    # fleet-summed EXPOSED boundary wait (stage 0 never receives, so
    # rank 0's own pvar alone would read 0)
    mine = _pv("pp_boundary_wait_seconds") - w0
    total = float(np.asarray(world.allreduce(
        np.array([[mine]], np.float32)))[0, 0])
    pp_res[leg] = {"t": best, "exposed_s": total, "out": outs}
mca_var.VARS.unset("progress_thread")
# parity witness: both schedules produce identical last-stage outputs
if me == S - 1:
    for a, b in zip(pp_res["nonblocking"]["out"],
                    pp_res["blocking"]["out"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

if me == 0:
    lines = [{
        "metric": "tree_planned_pass_speedup",
        "value": round(t_perleaf / max(t_planned, 1e-9), 4),
        "unit": "x_vs_blocking", "vs_baseline": None,
        "suite": "tree_overlap",
        "t_perleaf_s": round(t_perleaf, 5),
        "t_planned_s": round(t_planned, 5),
        "tree_bytes": int(tree_bytes),
        "leaves": len(leaves),
        "t_grad_s": round(t_grad, 5),
    }]
    for mode, r in results.items():
        suffix = "" if mode == "engine" else "_polling"
        lines.append({
            "metric": "tree_allreduce_hidden_frac" + suffix,
            "value": round(r["hidden_frac"], 4), "unit": "frac_hidden",
            "vs_baseline": None, "suite": "tree_overlap",
            "t_block_s": round(r["t_block"], 5),
            "t_overlap_s": round(r["t_ovl"], 5),
            "t_comm_s": round(t_planned, 5),
            "nbc_hidden_delta_s": round(r["nbc_hidden_s"], 5),
            "tree_hidden_delta_s": round(r["tree_hidden_s"], 5),
        })
    lines.append({
        "metric": "tree_overlap_speedup",
        "value": round(results["engine"]["t_block"]
                       / max(results["engine"]["t_ovl"], 1e-9), 4),
        "unit": "x_vs_blocking", "vs_baseline": None,
        "suite": "tree_overlap",
    })
    lines.append({
        "metric": "tree_pp_overlap_speedup",
        "value": round(pp_res["blocking"]["t"]
                       / max(pp_res["nonblocking"]["t"], 1e-9), 4),
        "unit": "x_vs_blocking", "vs_baseline": None,
        "suite": "tree_overlap",
        "t_blocking_s": round(pp_res["blocking"]["t"], 5),
        "t_nonblocking_s": round(pp_res["nonblocking"]["t"], 5),
        "exposed_blocking_s": round(pp_res["blocking"]["exposed_s"], 5),
        "exposed_nonblocking_s": round(
            pp_res["nonblocking"]["exposed_s"], 5),
        "microbatches": m, "stages": S,
    })
    lines.append({
        "metric": "tree_overlap_pvars", "value": None, "unit": None,
        "vs_baseline": None, "suite": "tree_overlap",
        "pvars": {k: v for k, v in pvar.PVARS.read_all().items()
                  if k.startswith(("tree_", "pp_boundary",
                                   "nbc_hidden"))},
        "cumulative": True,
    })
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f, default=str)
world.barrier()
mpi.finalize()
'''


def _tree_micro_suite():
    """tree_overlap lines: the planned whole-tree gradient pass vs the
    per-leaf loop at trainer scale — a REAL 3-process tpurun job
    computing actual models/transformer fwd/bwd gradients per step,
    syncing the full pytree through parallel/tree.TreeSync. Reports
    planned-vs-per-leaf comm speedup, exposed-vs-hidden comm fraction
    (engine vs polling; nbc_hidden_seconds/tree_hidden_seconds deltas
    are the witnesses), and the HostPipeline microbatch leg with
    nonblocking vs blocking stage boundaries. Gate direction: tree_*
    and frac_hidden are higher-better."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    lines = run_loopback_app(
        3, _TREE_BENCH_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__))},
        {}, "tree_bench.json", timeout_s=420)
    if lines is None:
        return [{"metric": "tree_overlap_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "tree_overlap bench job failed"}]
    return lines  # main()'s emit() stamps the tier label


#: worker app for the ft_recovery micro-suite: a REAL 3-process tpurun
#: job under the --ft-continue policy driving an ElasticStep training
#: loop; the sensor SIGKILLs rank 2 mid-run (kill cvars scoped by
#: rank), the survivors detect via the job-epoch bump, revoke+shrink,
#: roll back to the last committed checkpoint, and finish — process 0
#: writes the recovery-time/steps-lost lines plus the pvar witnesses.
_FT_BENCH_APP = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.ft.checkpoint import Checkpointer
from ompi_release_tpu.ft.sensor import FtTester
from ompi_release_tpu.parallel.elastic import ElasticStep

STEPS = int(os.environ.get("OMPITPU_FT_BENCH_STEPS", "8"))

world = mpi.init()
from ompi_release_tpu.runtime.runtime import Runtime
rt = Runtime.current()
me = rt.bootstrap["process_index"]

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

ckpt = Checkpointer(os.path.join(
    os.path.dirname(os.environ["OMPITPU_LOOPBACK_OUT"]),
    "ft_ckpt", "rank%%d" %% me))

def step_fn(step, state, comm):
    contrib = np.full((len(comm.local_comm_ranks), 4),
                      float(step + 1), np.float32)
    got = np.asarray(comm.allreduce(contrib))
    return np.asarray(state) + got[:1]

es = ElasticStep(world, step_fn, ckpt, policy="shrink",
                 checkpoint_every=1,
                 tester=FtTester.from_cvars(me))
t0 = time.perf_counter()
state, stats = es.run(np.zeros((1, 4), np.float32), STEPS)
wall = time.perf_counter() - t0

if me == 0:
    lines = [{
        "metric": "ft_recovery_seconds", "value": round(
            _pv("ft_recovery_seconds"), 4),
        "unit": "s", "vs_baseline": None, "suite": "ft_recovery",
        "procs": 3, "steps": STEPS, "wall_s": round(wall, 4),
        "failures_detected": _pv("ft_failures_detected"),
        "recoveries": _pv("ft_recoveries"),
        "revokes": _pv("ft_revokes"),
    }, {
        "metric": "ft_steps_lost", "value": stats["steps_lost"],
        "unit": "steps", "vs_baseline": None, "suite": "ft_recovery",
        "checkpoint_every": 1,
    }]
    assert _pv("ft_failures_detected") == 1.0, "expected ONE failure"
    assert _pv("ft_recoveries") == 1.0, "expected ONE recovery"
    with open(os.environ["OMPITPU_LOOPBACK_OUT"], "w") as f:
        json.dump(lines, f)
mpi.finalize()
'''


def _ft_micro_suite():
    """ft_recovery lines: wall time of one detect->revoke->shrink->
    rollback cycle and the steps recomputed after rollback, measured
    through a real 3-process tpurun job (--ft-continue policy) whose
    rank 2 is SIGKILLed by the armed sensor mid-run. Lower-better on
    both metrics — a recovery-time regression gates exactly like a
    latency regression (tpu_bench_gate METRIC_LOWER_BETTER_PREFIXES).
    Loopback-CPU either way: detection, wire reaps, and the shrink
    agreement are host-side paths."""
    import os

    from ompi_release_tpu.tools.tpurun import run_loopback_app

    lines = run_loopback_app(
        3, _FT_BENCH_APP % {"repo": os.path.dirname(
            os.path.abspath(__file__))},
        {"OMPITPU_FT_BENCH_STEPS": "8",
         "OMPITPU_MCA_sensor_ft_kill_step": "3",
         "OMPITPU_MCA_sensor_ft_kill_rank": "2"},
        "ft_bench.json", timeout_s=300,
        job_kw={"on_failure": "continue", "heartbeat_s": 0.3,
                "miss_limit": 4})
    if lines is None:
        return [{"metric": "ft_recovery_suite", "value": None,
                 "unit": None, "vs_baseline": None,
                 "error": "ft recovery bench job failed"}]
    return lines  # main()'s emit() stamps the tier label


def _fleet_micro_suite(sizes=(256, 1024)):
    """fleet_scaling lines: the simulated-fleet harness
    (ompi_release_tpu/testing/fleet_sim.py) runs the REAL
    hier_schedules round code at P simulated ranks over the virtual
    wire and emits the scaling observables the O(log P) claims rest
    on — bcast root sends, recursive-doubling rounds, Rabenseifner
    per-rank inter bytes, and the fabric-model makespan. Every line
    carries tier_label "sim": the numbers are deterministic functions
    of (schedule, fabric model), so the gate's per-(metric, tier) fit
    must never mix them with loopback-cpu/tpu wall-clock history —
    and within the sim tier a tripped bound IS a schedule regression
    (more rounds / more bytes), not noise. sim_* metrics are
    lower-better, topo_* (torus/multiring speedups over the flat
    ring) higher-better (tpu_bench_gate registers both prefixes).
    Device-free: no backend involved, jax never imported."""
    import math

    from ompi_release_tpu.coll import hier_schedules as hs
    from ompi_release_tpu.testing import fleet_sim as fs

    lines = []
    for P in sizes:
        fleet = fs.FleetSim(P, hosts_per=8, seed=1)
        procs = fleet.procs
        logp = fs.log2_rounds(P)

        def line(metric, value, unit, **kv):
            lines.append(dict(
                {"metric": f"{metric}_p{P}", "value": value,
                 "unit": unit, "vs_baseline": None,
                 "suite": "fleet_scaling", "tier_label": "sim",
                 "P": P, "hosts": math.ceil(P / 8)}, **kv))

        # binomial bcast: the root's O(log P) fan-out
        val = np.arange(16, dtype=np.int32)
        rep = fleet.run(
            lambda x, p: hs.bcast_binomial(
                x, procs, p, 0, val if p == 0 else None),
            label="bcast")
        line("sim_bcast_root_sends", rep.msgs_sent[0], "msgs",
             expect=logp)
        line("sim_bcast_makespan", round(rep.makespan * 1e3, 6),
             "sim_ms")

        # recursive-doubling partial exchange: ceil(log2 P) rounds
        data = {p: np.full(8, p + 1, np.int64) for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allgather_bruck(x, procs, p, data[p],
                                            [8] * P),
            label="allgather")
        line("sim_rd_rounds", rep.max_rounds(), "rounds",
             expect=logp)

        # Rabenseifner allreduce: ~2n(P-1)/P inter bytes per rank
        # (vs (P-1)n linear) in 2*ceil(log2 P) rounds
        n_el = 2 * P
        fdata = {p: np.arange(n_el, dtype=np.float32) * ((p % 7) + 1)
                 for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allreduce_rabenseifner(
                x, procs, p, fdata[p], np.add, 0.0),
            label="allreduce")
        line("sim_rab_bytes_per_rank", rep.max_bytes_sent(), "bytes",
             expect=fs.rabenseifner_bytes_per_rank(n_el, 4, P),
             payload_bytes=n_el * 4)
        line("sim_rab_rounds", rep.max_rounds(), "rounds",
             expect=2 * logp)
        line("sim_allreduce_makespan", round(rep.makespan * 1e3, 6),
             "sim_ms")

        # 2D-torus allreduce on the hosts_per=8 grid: DCN carries only
        # the 1/d0-sized partials — measured inter-host bytes equal
        # the closed form exactly, and the flat-ring baseline (also
        # closed form: H boundary NICs each shipping every chunk) is
        # strictly above it; topo_* = higher-better speedup ratios
        from ompi_release_tpu.coll import topo_schedules as ts

        d0, d1 = 8, P // 8
        n_t = 8 * P  # divisible by P, d0, d1: exact closed forms
        tdata = {p: np.arange(n_t, dtype=np.float32) * ((p % 5) + 1)
                 for p in procs}
        tfleet = fs.FleetSim(P, hosts_per=8, seed=1)
        host_of = tfleet.fabric.host_of
        rep_t = tfleet.run(
            lambda x, p: ts.allreduce_torus2d(
                x, procs, p, tdata[p], np.add, 0.0, host_of),
            label="allreduce_torus")
        torus_total = sum(rep_t.inter_bytes_sent.values())
        flat_total = ts.flat_ring_inter_bytes_total(n_t, 4, P, d1)
        line("sim_torus_inter_bytes_per_rank",
             max(rep_t.inter_bytes_sent.values()), "bytes",
             expect=ts.torus_inter_bytes_per_rank(n_t, 4, d0, d1),
             payload_bytes=n_t * 4)
        line("sim_torus_rounds", rep_t.max_rounds(), "rounds",
             expect=ts.torus_rounds(d0, d1))
        line("sim_torus_makespan", round(rep_t.makespan * 1e3, 6),
             "sim_ms")
        line("topo_torus_inter_bytes_x",
             round(flat_total / torus_total, 6), "x_inter_bytes")
        if P <= 256:
            # the flat-ring ACTUAL run (2(P-1) rounds — affordable at
            # this P) anchors the virtual-makespan speedup
            rfleet = fs.FleetSim(P, hosts_per=8, seed=1)
            rep_r = rfleet.run(
                lambda x, p: hs.allreduce_ring(
                    x, procs, p, tdata[p], np.add, 0.0),
                label="allreduce_ring")
            line("topo_torus_makespan_x",
                 round(rep_r.makespan / rep_t.makespan, 6),
                 "x_makespan")
            # multiring: k disjoint stride rings driven in parallel —
            # the k× ring-bandwidth claim, on a bandwidth-bound
            # UNIFORM wire (striping is topology-oblivious; the torus
            # is the hierarchy answer)
            def bw_fleet():
                return fs.FleetSim(P, fabric=fs.Fabric(
                    P, hosts_per=P, intra=fs.LinkSpec(1e-7, 0.1),
                    seed=1))

            f_r = bw_fleet()
            rep_br = f_r.run(
                lambda x, p: hs.allreduce_ring(
                    x, procs, p, tdata[p], np.add, 0.0),
                label="allreduce_ring_bw")
            f_m = bw_fleet()
            rep_bm = f_m.run(
                lambda x, p: ts.allreduce_multiring(
                    x, procs, p, tdata[p], np.add, 0.0, 4),
                label="allreduce_multiring_bw")
            line("topo_multiring_makespan_x",
                 round(rep_br.makespan / rep_bm.makespan, 6),
                 "x_makespan")
    return lines


def _multi_tenant_micro_suite(sizes=(256,)):
    """multi_tenant lines: the service plane's fairness story on the
    deterministic fleet simulator (testing/scenarios.multi_tenant) —
    N tenants x small fleets over ONE shared fabric. Three legs per
    P: the latency tenant SOLO (full wire), both tenants contended
    under the weighted-fair QoS shares (latency:8,bulk:2), and the
    same contention on a FIFO (no-QoS) wire. The headline ratio
    ``tenant_latency_isolation`` = contended-p99 / solo-p99 is THE
    gate-checked degradation factor of acceptance: bounded by
    1/fair_share (1.25x at 8:2) + the schedule margin, where the
    FIFO wire blows to ~hosts_per x. tenant_* metrics are
    lower-better (tpu_bench_gate registers the prefix); tier "sim"
    keeps the deterministic numbers out of wall-clock fits.
    Device-free: no backend involved."""
    from ompi_release_tpu.testing import scenarios as sc

    lines = []
    for P in sizes:
        r = sc.multi_tenant(P=P, seed=1, kill_bulk=False)

        def line(metric, value, unit, **kv):
            lines.append(dict(
                {"metric": f"{metric}_p{P}", "value": value,
                 "unit": unit, "vs_baseline": None,
                 "suite": "multi_tenant", "tier_label": "sim",
                 "P": P, "classes": "latency:8,bulk:2"}, **kv))

        solo_p99 = r.p99(r.solo_durations)
        qos_p99 = r.p99(r.qos_durations)
        fifo_p99 = r.p99(r.fifo_durations)
        bulk_p99 = r.p99(r.bulk_durations)
        line("tenant_lat_solo_p99", round(solo_p99 * 1e3, 6),
             "sim_ms", qos="latency")
        line("tenant_lat_contended_p99", round(qos_p99 * 1e3, 6),
             "sim_ms", qos="latency")
        line("tenant_lat_fifo_p99", round(fifo_p99 * 1e3, 6),
             "sim_ms", qos="latency")
        line("tenant_bulk_contended_p99", round(bulk_p99 * 1e3, 6),
             "sim_ms", qos="bulk")
        # THE acceptance ratio: contended/solo p99 under QoS, bounded
        # by the latency class's inverse fair share...
        line("tenant_latency_isolation",
             round(qos_p99 / solo_p99, 6), "p99_ratio",
             bound=round(1.0 / r.share_lat, 6), qos="latency")
        # ...vs what the same contention costs on a fair-less wire
        # (the head-of-line factor QoS buys back)
        line("tenant_fifo_hol_ratio",
             round(fifo_p99 / solo_p99, 6), "p99_ratio", qos="latency")
        assert qos_p99 <= solo_p99 / r.share_lat * 1.10, \
            "isolation bound violated in-suite"
    return lines


def _sweep_lines(specs, ceiling_names, slopes, n):
    """Metric lines + headline from the sweep's slope matrix
    ``(n_specs, rounds)``."""
    # per-round bandwidths; ceiling_r = best bw ANY copy candidate or
    # the line itself achieved that round (vs_baseline <= 1.0 by
    # construction; see module docstring)
    bw = {}
    for i, s in enumerate(specs):
        if s["nbytes"] is not None:
            bw[s["name"]] = s["nbytes"] / slopes[i] / 1e9
    cand = np.stack([bw[nm] for nm in ceiling_names])
    ceil_r = cand.max(axis=0)
    ceil_med = float(np.median(ceil_r))
    # the CV must be robust to a contaminated round: a host hiccup
    # (or a concurrent job on the chip) can drive one round's slope to
    # the 1e-12 clamp, producing an absurd per-round bandwidth that
    # explodes a plain std while the median stays sane — compute
    # variability over rounds within a sane band of the median and
    # surface how many rounds were discarded
    sane = ceil_r[(ceil_r > 0.2 * ceil_med) & (ceil_r < 5 * ceil_med)]
    dropped_rounds = int(ceil_r.size - sane.size)
    if sane.size:
        ceil_cv = float(np.std(sane) / max(float(np.median(sane)), 1e-12))
    else:
        ceil_cv = float("nan")

    lines = []
    headline = None
    for i, s in enumerate(specs):
        nm = s["name"]
        if nm.startswith("ceiling_copy"):
            continue  # ceiling candidates feed the denominator only
        if s["nbytes"] is None:  # latency line (ring)
            per_hop = np.median(slopes[i]) / s["hops"] * 1e6
            lines.append({
                "metric": f"{nm}_latency", "value": round(per_hop, 4),
                "unit": "us/hop", "vs_baseline": None,
                "note": "no published ref latency; tracked across rounds",
            })
            continue
        value = float(np.median(bw[nm]))
        if s.get("unstable"):
            lines.append({
                "metric": nm, "value": round(value, 3), "unit": "GB/s",
                "vs_baseline": None, "unstable": True,
                "note": "K-delta inside host jitter; value unreliable",
            })
            continue
        if value > 1.15 * ceil_med and s.get("ws", float("inf")) \
                <= ONCHIP_WS:
            # working set fits on-chip: the loop legitimately runs at
            # VMEM bandwidth (iterations checksum-verified), so an HBM
            # ratio would be meaningless — label the tier instead of
            # faking a ceiling.  The ws gate keeps a lucky round from
            # misfiling an HBM-bound line (a 256 MiB transpose at
            # ceiling parity + the +-20% wobble can median past
            # 1.15x): only working sets that can physically reside in
            # VMEM are eligible for the tier; everything else takes
            # the vs_baseline path, whose per-round max(ceil, self)
            # already handles value > ceiling honestly
            entry = {
                "metric": nm, "value": round(value, 3), "unit": "GB/s",
                "vs_baseline": None, "tier": "on-chip",
                "ceiling_gbps": round(ceil_med, 1),
            }
            lines.append(entry)
            continue
        line_ceil = np.maximum(ceil_r, bw[nm])
        vs = float(np.median(bw[nm] / line_ceil))
        entry = {
            "metric": nm,
            "value": round(value, 3),
            "unit": "GB/s",
            "vs_baseline": round(vs, 4),
            "ceiling_gbps": round(ceil_med, 1),
            "ceiling_cv": round(ceil_cv, 4),
        }
        if dropped_rounds:
            entry["ceiling_rounds_dropped"] = dropped_rounds
        if nm == "allreduce_256MiB" and n < 2:
            headline = {
                "metric": "op_sum_256MiB_f32_hbm_bw",
                "value": entry["value"], "unit": "GB/s",
                "vs_baseline": entry["vs_baseline"],
                "ceiling_gbps": entry["ceiling_gbps"],
                "ceiling_cv": entry["ceiling_cv"],
                "parity": True,
            }
        elif nm == "allreduce_256MiB" and n >= 2:
            headline = {
                "metric": f"allreduce_256MiB_f32_busbw_{n}dev",
                "value": entry["value"], "unit": "GB/s",
                "vs_baseline": entry["vs_baseline"],
                "ceiling_gbps": entry["ceiling_gbps"],
                "ceiling_cv": entry["ceiling_cv"],
                "parity": True,
            }
        lines.append(entry)

    if headline is None:  # CPU dev runs (truncated sweep): largest point
        biggest = max(
            (s for s in specs if s["nbytes"] is not None
             and s["name"].startswith("allreduce_")),
            key=lambda s: s["nbytes"],
        )
        headline = {
            "metric": "op_sum_small_f32_hbm_bw" if n < 2
            else f"allreduce_f32_busbw_{n}dev",
            "value": round(float(np.median(bw[biggest["name"]])), 3),
            "unit": "GB/s",
            "vs_baseline": round(float(np.median(
                bw[biggest["name"]]
                / np.maximum(ceil_r, bw[biggest["name"]]))), 4),
            "ceiling_gbps": round(ceil_med, 1),
            "ceiling_cv": round(ceil_cv, 4),
            "parity": True,
        }
        if dropped_rounds:
            headline["ceiling_rounds_dropped"] = dropped_rounds
    return lines, headline


def main():
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    n = len(devices)
    on_tpu = devices[0].platform == "tpu"
    from ompi_release_tpu.runtime.ess import host_platform_declared

    if not on_tpu and not host_platform_declared():
        # no fallback: a CPU run is one that was asked for by name
        raise SystemExit(
            f"bench: no accelerator — jax came up on "
            f"{devices[0].platform}; only an explicit JAX_PLATFORMS=cpu "
            "run benches the CPU (and labels every line loopback-cpu)")

    if n >= 2:
        specs, ceiling_names = _mesh_specs(jax, jnp, devices, on_tpu)
    else:
        specs, ceiling_names = _single_chip_specs(
            jax, jnp, devices[0], on_tpu
        )

    if on_tpu:
        # compile/warm at the static guess, then size K from measured
        # per-iteration time (VMEM-resident loops are 5-20x faster
        # than the HBM estimate)
        for s in specs:
            s["k_lo"], s["k_hi"] = _calibrate_k(
                s["loop"], s["args"], s["k_hi"]
            )

    rounds = 5 if on_tpu else 3

    tier = "tpu" if on_tpu else "loopback-cpu"

    def emit(ln):
        # explicit tier label on EVERY line: tpu runs and explicit
        # JAX_PLATFORMS=cpu runs stay comparable within their own tier
        ln.setdefault("tier_label", tier)
        print(json.dumps(ln), flush=True)
        if ln.get("error"):
            # a phase that could not produce its number fails the run
            raise RuntimeError(
                f"bench phase {ln.get('metric')} failed: {ln['error']}")

    slopes = _run_rounds(specs, rounds)
    lines, headline = _sweep_lines(specs, ceiling_names, slopes, n)
    for ln in lines:
        emit(ln)

    # compute-bound line (single-chip fwd+bwd MFU): measured after the
    # bandwidth sweep so its compile time cannot contaminate those
    # loops' interleaved rounds
    emit(_mfu_metric(jax, jnp, devices[0], on_tpu, rounds=max(3, rounds)))

    # micro-suites (every multi-process one runs its ranks as explicit
    # JAX_PLATFORMS=cpu host ranks: this process holds the chip, and a
    # chip belongs to one process at a time):
    #   coll: pipeline/fusion framework-driver lines with pvar labels
    #   wire: cross-process p2p bandwidth, HOL lanes, allgatherv overlap
    #   hier: spanning-collective inter schedules at 4 loopback procs
    #   overlap: exposed vs hidden comm time for iallreduce buckets
    #            under the async progress engine vs polling fallback
    #   tree_overlap: planned whole-tree gradient pass vs per-leaf
    #            loop on a real transformer trainer, hidden-comm
    #            fraction + nonblocking pipeline boundaries
    #   ft_recovery: detect->revoke->shrink->rollback wall time of a
    #            3-proc job whose rank 2 is SIGKILLed mid-run
    #   sentinel: contract-sentinel overhead, enabled vs disabled,
    #            with the sentinel_ops_hashed pvar as witness
    #   fleet_scaling: the simulated-fleet harness runs the real
    #            hier_schedules at P=256/1024 virtual ranks and emits
    #            sim_* scaling observables (rounds, bytes/rank,
    #            makespan), tier_label "sim", all gate-guarded
    #   multi_tenant: the service plane's fairness story — latency
    #            tenant p99 solo vs contended-under-QoS vs FIFO on
    #            one shared simulated fabric; the gate-checked
    #            tenant_latency_isolation degradation ratio
    #   steady_state: interpreted-vs-compiled Python-orchestration
    #            time (frozen schedule plans, coll/plan) for one-shot,
    #            persistent, and 3-proc spanning allreduce legs
    #   native_rounds: the native C plan executor vs the PlannedXchg
    #            Python replay vs interpreted, 3-proc loopback:
    #            orchestration split per leg, bitwise parity in-app,
    #            the >= 2x orch-speedup acceptance at <= 256 KiB
    #   rma_steady: the one-sided twin (frozen epoch plans, osc/plan)
    #            — interpreted-vs-planned fence epochs plus the
    #            planned symmetric-heap bulk path vs per-call
    for suite in (
        _coll_micro_suite, _steady_state_micro_suite,
        _native_rounds_micro_suite, _rma_steady_micro_suite,
        _sentinel_micro_suite,
        _wire_micro_suite, _native_wire_micro_suite,
        _native_obs_micro_suite, _hier_micro_suite,
        _overlap_micro_suite, _tree_micro_suite, _ft_micro_suite,
        _fleet_micro_suite, _multi_tenant_micro_suite,
    ):
        for ln in suite():
            emit(ln)

    # ONE cumulative snapshot: the configs run interleaved (see
    # _run_rounds), so per-config pvar deltas do not exist — emitting
    # the same blob per line would only masquerade as them
    snapshot = json.dumps(
        {"pvars": _pvar_snapshot(), "cumulative": True}, default=str
    )
    headline.setdefault("tier_label", tier)
    print(snapshot, flush=True)
    print(json.dumps(headline), flush=True)  # headline stays LAST


if __name__ == "__main__":
    sys.exit(main())
