#!/usr/bin/env python3
"""chip_smoke.py — the library's own entry points, on the chip, once.

    python chip_smoke.py            # on a machine with 1 or more TPU chips

Three legs, each in its own process (a chip belongs to one process at a
time; this parent never imports jax):

  1. driver    one process, all local chips: ``mpi.init()`` -> world;
               allreduce / bcast / allgather / reduce_scatter_block
               (f32) and alltoall (int32) on device-resident buffers at
               8 B .. 256 MiB per rank, iallreduce+wait, send/recv of a
               device payload, a window fence epoch, an OSHMEM put/get,
               ``reduce_local`` through the Pallas SUM, and (>= 2 chips)
               the tuned ring with the Pallas SUM inside shard_map.
               Then every ``examples/*_tpu.py`` as it is.
  2. spanning  ``tpurun -n N``: one rank per chip (N = chips), or on a
               one-chip machine N = 2 with rank 1 an EXPLICIT host rank.
               Starts from an empty ``native/build/``.
  3. trainer   ``models/transformer.py`` at ``ModelConfig()`` defaults,
               batch 8 x seq 2048, adamw, 5 steps; on >= 2 chips the
               tp x dp factorization, on >= 4 also sp x ep (4 experts).

Every result is compared with a plain numpy reference. Integer-valued
data makes every sum exact, so collectives compare with ``==``; the
flash-attention kernels are compared, at the trainer's own widths, with
ring attention through the same model (loss and every gradient, at a
written tolerance). The run fails if any leg or example fails, if a rank that was
not declared a host rank is not on a TPU, or if any Pallas kernel was
built with ``interpret=True``. It computes no rate and no ratio.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no accelerator it exits non-zero and prints no such line.

``--rehearse-cpu N`` is a CPU rehearsal on N virtual devices at toy
sizes, for debugging this script; its output is labelled as such and is
never a chip result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MARK = "SMOKE-RESULT "      # a child's result, one JSON object
DEVICE_MARK = "SMOKE-DEVICE "  # what jax found, printed before any work
KiB, MiB = 1 << 10, 1 << 20
BUDGET_S = 1150.0  # the whole run, compilation included (limit: 1200)

DRIVER_BYTES = (8, 64 * KiB, MiB, 16 * MiB, 256 * MiB)
SPANNING_BYTES = (4 * KiB, MiB, 64 * MiB)
REHEARSAL_BYTES = (8, 64 * KiB, MiB)  # driver leg, CPU rehearsal only
NATIVE_PVARS = ("wire_native_bytes", "wire_native_frames",
                "wire_native_fallback_copies", "plan_native_fires",
                "plan_native_fallbacks")


# ---------------------------------------------------------------------------
# parent: orchestrates the legs, never touches jax
# ---------------------------------------------------------------------------

def _run(name, argv, env, deadline):
    """One child to completion inside the run's deadline. Returns
    (ok, {mark: [dicts]}, seconds). The child runs in its own session
    so a timeout kills it together with everything it started."""
    left = deadline - time.monotonic()
    if left <= 5:
        print(f"[{name}] FAILED: no time left in the {BUDGET_S:.0f}s budget")
        return False, {MARK: [], DEVICE_MARK: []}, 0.0
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=left)
        why = f"exit code {proc.returncode}"
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        why = "killed at the run's deadline"
    finally:
        try:  # whatever the child left behind in its session
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    dt = time.monotonic() - t0
    marked = {m: [json.loads(ln.split(m, 1)[1])
                  for ln in out.splitlines() if m in ln]
              for m in (MARK, DEVICE_MARK)}
    ok = proc.returncode == 0
    if not ok:
        print(out[-6000:])
        print(f"[{name}] FAILED: {why}", flush=True)
    return ok, marked, dt


def _leg_line(name, r):
    d = r["device"]
    print(f"[{name}] platform={d['platform']} device_kind={d['kind']!r} "
          f"devices={d['count']} compile_s={r['compile_s']:.2f} "
          f"cache_hits={r['cache_hits']} run_s={r['run_s']:.2f} "
          f"checks={r['checks']} pallas_calls={r['pallas_calls']} "
          f"pallas_interpreted={r['pallas_interpreted']}", flush=True)
    by_op = {}
    for row in r.get("table", ()):
        by_op.setdefault(row["op"], []).append(
            f"{row['bytes_per_rank']}B={row['first_call_s']:.4f}"
            + (f"/{row['second_call_s']:.5f}"
               if "second_call_s" in row else ""))
    for op, cells in by_op.items():
        print(f"[{name}] {op} first/second call s: " + " ".join(cells),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", type=int, default=0, metavar="N",
                    help="CPU rehearsal on N virtual devices at toy "
                         "sizes (labelled; never a chip result)")
    ap.add_argument("--leg", choices=("driver", "spanning", "trainer"),
                    help=argparse.SUPPRESS)  # child mode
    ap.add_argument("--host-ranks", default="", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.leg:
        return {"driver": leg_driver, "spanning": leg_spanning,
                "trainer": leg_trainer}[a.leg](a)

    if not os.path.isdir(os.path.join(REPO, "ompi_release_tpu")):
        print("chip_smoke: the ompi_release_tpu package is not next to "
              "this script — nothing to smoke", file=sys.stderr)
        return 2
    rehearsal = a.rehearse_cpu > 0
    if rehearsal:
        print(f"*** CPU REHEARSAL on {a.rehearse_cpu} virtual devices, toy "
              "sizes — NOT a chip result ***", flush=True)
    deadline = time.monotonic() + BUDGET_S
    env = dict(os.environ)
    child = [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--seed", str(a.seed)]
    if rehearsal:
        child += ["--rehearse-cpu", str(a.rehearse_cpu)]
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{a.rehearse_cpu}")
    results = []  # every child's result, for the totals
    failed = []

    # -- leg 1: driver mode ------------------------------------------------
    ok, out, dt = _run("driver", child + ["--leg", "driver"], env, deadline)
    if not out[DEVICE_MARK]:
        print("chip_smoke: FAILED: the driver leg found no device to "
              "report", file=sys.stderr)
        return 1
    device = out[DEVICE_MARK][-1]  # sizes the other legs
    if not rehearsal and device["platform"] != "tpu":
        print(f"chip_smoke: FAILED: no accelerator — jax came up on "
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    if ok and out[MARK]:
        _leg_line("driver", out[MARK][-1])
        results.append(out[MARK][-1])
    else:
        failed.append("driver")

    examples = sorted(f for f in os.listdir(os.path.join(REPO, "examples"))
                      if f.endswith("_tpu.py"))
    for ex in examples:
        ok, _, dt = _run(ex, [sys.executable, f"examples/{ex}"], env,
                         deadline)
        print(f"[example {ex}] {'OK' if ok else 'FAILED'} wall_s={dt:.1f}",
              flush=True)
        if not ok:
            failed.append(ex)

    # -- leg 2: spanning world under tpurun --------------------------------
    # one rank per chip; a one-chip machine gets rank 1 as an explicit
    # host rank (the worker pins JAX_PLATFORMS=cpu for the ranks named)
    nranks = max(2, device["count"])
    host_ranks = "" if device["count"] >= 2 else "1"
    build = os.path.join(REPO, "native", "build")
    shutil.rmtree(build, ignore_errors=True)
    print(f"[spanning] native/build/ emptied: tpurun builds "
          f"libompitpu_native.so from native/*.cc + Makefile here",
          flush=True)
    span_env = dict(env)
    span_env.pop("XLA_FLAGS", None)  # rehearsal: one device per rank too
    left = max(30, int(deadline - time.monotonic()) - 10)
    ok, out, dt = _run(
        "spanning",
        # the launcher's defaults otherwise (heartbeat 4 x 0.5 s)
        [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
         "-n", str(nranks), "--timeout", str(left)]
        + child + ["--leg", "spanning", "--host-ranks", host_ranks],
        span_env, deadline)
    built = os.path.exists(os.path.join(build, "libompitpu_native.so"))
    res = sorted(out[MARK], key=lambda r: r["rank"])
    if ok and len(res) == nranks and built:
        for r in res:
            name = (f"spanning rank {r['rank']}"
                    + (" (declared host rank)" if r["host_rank"] else ""))
            _leg_line(name, r)
            print(f"[{name}] "
                  + " ".join(f"{k}={r['pvars'][k]}" for k in NATIVE_PVARS),
                  flush=True)
        chips = [(r["chip"], tuple(r["chip_nodes"]))
                 for r in res if not r["host_rank"]]
        results += res
        print(f"[spanning] {nranks} ranks; (bound chip, device nodes held) "
              f"per chip rank: {chips}; libompitpu_native.so built here; "
              f"wall_s={dt:.1f}", flush=True)
        nodes = [nd for _, held in chips for nd in held]
        if (len({ch for ch, _ in chips}) != len(chips)
                or len(set(nodes)) != len(nodes)):
            print("[spanning] FAILED: two ranks share a chip")
            failed.append("spanning")
    else:
        print(f"[spanning] FAILED: ok={ok} results={len(res)}/{nranks} "
              f"native_built={built}")
        failed.append("spanning")

    # -- leg 3: trainer ----------------------------------------------------
    ok, out, dt = _run("trainer", child + ["--leg", "trainer"], env,
                       deadline)
    if ok and out[MARK]:
        res = out[MARK]
        _leg_line("trainer", res[-1])
        for run in res[-1]["runs"]:
            print(f"[trainer] axes={run['axes']} attn={run['attn']} "
                  f"loss {run['losses'][0]:.4f} -> {run['losses'][-1]:.4f} "
                  f"first_step_s={run['step_s'][0]:.2f} "
                  f"later_step_s={min(run['step_s'][1:]):.3f}", flush=True)
            if run["flash_vs_ring"]:
                p = run["flash_vs_ring"]
                print(f"[trainer] flash vs ring attention, tokens "
                      f"{p['tokens']}: loss {p['loss_flash']:.5f} vs "
                      f"{p['loss_ring']:.5f} (rel {p['loss_rel']:.2e}), "
                      f"worst gradient {p['grad_worst_leaf']} off by "
                      f"{p['grad_rel_worst']:.2e} of its norm", flush=True)
        results.append(res[-1])
    else:
        failed.append("trainer")

    print(f"[total] compile_s={sum(r['compile_s'] for r in results):.2f} "
          f"cache_hits={sum(r['cache_hits'] for r in results)} "
          f"wall_s={BUDGET_S - (deadline - time.monotonic()):.1f}",
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if rehearsal:
        result["rehearsal"] = "cpu"
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: everything below imports jax
# ---------------------------------------------------------------------------

class _Child:
    """What every leg shares: the counted ``pallas_call``, jax's own
    compile-time events, the device triple, and the check counter."""

    def __init__(self, a, expect_tpu=True):
        import collections

        sys.path.insert(0, REPO)
        import jax
        import jax.monitoring
        from jax.experimental import pallas as pl

        self.jax = jax
        self.rehearsal = a.rehearse_cpu > 0
        self.pallas = {"calls": 0, "interpreted": 0}
        orig = pl.pallas_call

        def counted(*args, **kw):
            self.pallas["calls"] += 1
            self.pallas["interpreted"] += bool(kw.get("interpret"))
            return orig(*args, **kw)

        pl.pallas_call = counted  # the library calls pl.pallas_call(...)
        self.durations = collections.defaultdict(float)
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: self.durations.__setitem__(
                ev, self.durations[ev] + dur))
        jax.monitoring.register_event_listener(
            lambda ev, **kw: self.events.update([ev]))
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        print(DEVICE_MARK + json.dumps(self.device), flush=True)
        if expect_tpu and not self.rehearsal and devs[0].platform != "tpu":
            raise SystemExit(
                f"chip_smoke: no accelerator — jax came up on "
                f"{devs[0].platform!r}")
        self.checks = 0
        self.run_s = 0.0

    def timed(self, fn):
        t0 = time.perf_counter()
        out = self.jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    def equal(self, got, want, what):
        import numpy as np

        got = np.asarray(got)
        if got.shape != want.shape or not np.array_equal(got, want):
            raise SystemExit(f"chip_smoke: {what}: result differs from "
                             "the numpy reference")
        self.checks += 1

    def on_devices(self, arr, devices, what):
        if not isinstance(arr, self.jax.Array):
            raise SystemExit(f"chip_smoke: {what}: output is "
                             f"{type(arr).__name__}, not a device array")
        if set(arr.sharding.device_set) != set(devices):
            raise SystemExit(
                f"chip_smoke: {what}: output lives on "
                f"{sorted(d.id for d in arr.sharding.device_set)}, expected "
                f"{sorted(d.id for d in devices)}")
        self.checks += 1

    def finish(self, **extra):
        if self.pallas["interpreted"] and not self.rehearsal:
            raise SystemExit(
                f"chip_smoke: {self.pallas['interpreted']} Pallas kernel(s) "
                "were built with interpret=True on the chip")
        print(MARK + json.dumps(dict(
            device=self.device,
            compile_s=self.durations[
                "/jax/core/compile/backend_compile_duration"],
            cache_hits=self.events["/jax/compilation_cache/cache_hits"],
            run_s=self.run_s, checks=self.checks,
            pallas_calls=self.pallas["calls"],
            pallas_interpreted=self.pallas["interpreted"], **extra)),
            flush=True)
        return 0


def _ints(rng, shape, dtype):
    """Small integers: every sum of them is exact in f32 and int32, so
    results compare with == whatever order a collective reduces in."""
    import numpy as np

    return rng.integers(-8, 9, size=shape, dtype=np.int8).astype(dtype)


def _collective_sweep(c, x_rows, sizes, cases, table, devices=None):
    """Each case at each size, twice: the first call compiles, the
    second is the run that is timed; the first result is compared (and,
    given ``devices``, checked to live on exactly those)."""
    for nbytes in sizes:
        for name, make, call, ref in cases:
            x = make(nbytes)
            xd = x_rows(x)
            out, first = c.timed(lambda: call(xd))
            if devices is not None:
                c.on_devices(out, devices, f"{name} {nbytes} B")
            c.equal(out, ref(x), f"{name} {nbytes} B")
            del out
            out, again = c.timed(lambda: call(xd))
            c.run_s += again
            del out, xd, x
            table.append({"op": name, "bytes_per_rank": nbytes,
                          "first_call_s": first, "second_call_s": again})


def leg_driver(a) -> int:
    c = _Child(a)
    jax = c.jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import ompi_release_tpu as mpi
    from ompi_release_tpu import ops
    from ompi_release_tpu.mca import var as mca_var
    from ompi_release_tpu.ops import op as op_mod
    from ompi_release_tpu.osc.window import win_allocate
    from ompi_release_tpu.oshmem import shmem
    from ompi_release_tpu.runtime.runtime import Runtime

    world = mpi.init()
    n = world.size
    devs = list(world.submesh.devices.flat)
    if n != c.device["count"]:
        raise SystemExit(f"chip_smoke: world.size {n} != "
                         f"{c.device['count']} local devices")
    if not c.rehearsal:
        for ep in Runtime.current().endpoints:
            if ep.platform != "tpu":
                raise SystemExit(f"chip_smoke: endpoint {ep.rank} is on "
                                 f"{ep.platform!r}, not a TPU")
    sharding = NamedSharding(world.submesh, P("rank"))
    rng = np.random.default_rng(a.seed)
    put = lambda x: jax.device_put(x, sharding)  # noqa: E731

    def f32(nbytes, multiple=1):
        elems = -(-max(1, nbytes // 4) // multiple) * multiple
        return _ints(rng, (n, elems), np.float32)

    def by_dest(x):  # row r = every rank's r-th chunk, in rank order
        return x.reshape(n, n, -1).transpose(1, 0, 2).reshape(n, -1)

    cases = [
        ("allreduce_f32", f32, lambda x: world.allreduce(x, ops.SUM),
         lambda x: np.broadcast_to(x.sum(0), x.shape)),
        ("bcast_f32", f32, lambda x: world.bcast(x, root=n - 1),
         lambda x: np.broadcast_to(x[n - 1], x.shape)),
        ("allgather_f32", f32, world.allgather,
         lambda x: np.broadcast_to(x.reshape(-1), (n, x.size))),
        ("reduce_scatter_block_f32", lambda b: f32(b, n),
         lambda x: world.reduce_scatter_block(x, ops.SUM),
         lambda x: x.sum(0).reshape(n, -1)),
        ("alltoall_i32", lambda b: f32(b, n).astype(np.int32),
         world.alltoall, by_dest),
    ]
    table = []
    _collective_sweep(c, put,
                      REHEARSAL_BYTES if c.rehearsal else DRIVER_BYTES,
                      cases, table, devices=devs)

    # nonblocking: post, then wait
    x = f32(MiB)
    req = world.iallreduce(put(x), ops.SUM)
    req.wait()
    c.equal(req.value, np.broadcast_to(x.sum(0), x.shape), "iallreduce")

    # p2p of a device-resident payload: rank 0 -> rank n-1
    pay = _ints(rng, (MiB // 4,), np.float32)
    world.send(jax.device_put(pay, devs[0]), n - 1, tag=7, rank=0)
    got, st = world.recv(source=0, tag=7, rank=n - 1)
    c.equal(got, pay, "send/recv")
    if st.source != 0:
        raise SystemExit("chip_smoke: recv status names the wrong source")

    # one-sided: a fence epoch with a put and a get
    slot = _ints(rng, (64 * KiB // 4,), np.float32)
    win = win_allocate(world, slot.shape, np.float32)
    win.fence()
    win.put(jax.device_put(slot, devs[0]), target=n - 1)
    g = win.get(target=n - 1)
    win.fence()
    c.equal(g.value, slot, "win.get after put")
    c.equal(np.asarray(win.read())[n - 1], slot, "win.read after put")
    win.fence_end()
    win.free()

    # OSHMEM put/get on the symmetric heap
    ctx = shmem.shmem_init()
    sym = ctx.malloc(slot.shape, np.float32)
    ctx.barrier_all()
    ctx.put(sym, jax.device_put(slot, devs[0]), pe=n - 1)
    ctx.quiet()
    c.equal(ctx.get(sym, pe=n - 1), slot, "shmem get after put")
    sym.free()
    shmem.shmem_finalize()

    # the op framework's accelerated SUM: >= 4 MiB f32 resolves to the
    # Pallas streaming kernel, and that kernel is what runs
    before = c.pallas["calls"]
    big = 4 * MiB
    a1, b1 = (_ints(rng, (big // 4,), np.float32) for _ in range(2))
    if op_mod.resolve(ops.SUM, np.float32, big).name != "sum[pallas]":
        raise SystemExit("chip_smoke: 4 MiB f32 SUM did not resolve to the "
                         "pallas op component")
    out, _ = c.timed(lambda: op_mod.reduce_local(
        jax.device_put(a1, devs[0]), jax.device_put(b1, devs[0]), ops.SUM))
    c.equal(out, a1 + b1, "reduce_local (pallas SUM)")
    if c.pallas["calls"] == before:
        raise SystemExit("chip_smoke: reduce_local ran no Pallas kernel")

    # the tuned ring at >= 16 MiB: the Pallas SUM compiles INSIDE
    # shard_map, next to ppermute (needs a ring of more than one)
    if n >= 2:
        mca_var.set_value("coll", "tuned")
        try:
            tuned = world.dup(name="smoke_tuned")
        finally:
            mca_var.VARS.unset("coll")
        mca_var.set_value("coll_tuned_allreduce_algorithm", "ring")
        try:
            before = c.pallas["calls"]
            x = f32(MiB if c.rehearsal else 16 * MiB, n)
            # the pallas component claims per-rank reductions >= 4 MiB
            out, first = c.timed(lambda: tuned.allreduce(put(x), ops.SUM))
            c.on_devices(out, devs, "tuned ring allreduce")
            c.equal(out, np.broadcast_to(x.sum(0), x.shape),
                    "tuned ring allreduce")
            if not c.rehearsal and c.pallas["calls"] == before:
                raise SystemExit("chip_smoke: the forced tuned ring built "
                                 "no Pallas kernel")
            table.append({"op": "tuned_ring_allreduce_f32",
                          "bytes_per_rank": x.nbytes // n,
                          "first_call_s": first})
        finally:
            mca_var.VARS.unset("coll_tuned_allreduce_algorithm")
            tuned.free()

    # what coll/driver.py does with a HOST buffer (written down for
    # ROADMAP S1, not judged): where jnp.asarray commits it
    import jax.numpy as jnp

    host = f32(MiB)
    placement = {
        "host_input_committed_to": sorted(
            d.id for d in jnp.asarray(host).sharding.device_set),
        "output_devices": sorted(
            d.id for d in world.allreduce(host).sharding.device_set),
    }
    mpi.finalize()
    return c.finish(table=table, placement=placement)


def leg_spanning(a) -> int:
    rank = int(os.environ["OMPITPU_NODE_ID"]) - 1
    host_ranks = {int(r) for r in a.host_ranks.split(",") if r}
    if rank in host_ranks:
        # an EXPLICIT host rank: declared here, before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
    c = _Child(a, expect_tpu=rank not in host_ranks)
    jax = c.jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import ompi_release_tpu as mpi
    from ompi_release_tpu import ops
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.osc.window import win_allocate
    from ompi_release_tpu.oshmem import shmem
    from ompi_release_tpu.runtime.runtime import Runtime

    world = mpi.init()
    rt = Runtime.current()
    if not (rt.unified and world.spans_processes):
        raise SystemExit("chip_smoke: tpurun did not form a unified world")
    n, off, local_n = world.size, rt.local_rank_offset, rt.local_size
    first, last = off == 0, off + local_n == n
    for ep in rt.endpoints:
        declared = ep.process_index in host_ranks or c.rehearsal
        if ep.platform != "tpu" and not declared:
            raise SystemExit(
                f"chip_smoke: rank {ep.rank} (process {ep.process_index}) "
                f"came up on {ep.platform!r} and was not declared a host "
                "rank")
    local = jax.local_devices()
    sharding = NamedSharding(world.submesh, P("rank"))
    rng = np.random.default_rng(a.seed)  # same stream in every rank
    rows = slice(off, off + local_n)
    put = lambda x: jax.device_put(x[rows], sharding)  # noqa: E731

    def f32(nbytes):
        return _ints(rng, (n, max(1, nbytes // 4)), np.float32)

    cases = [
        ("allreduce_f32", f32, lambda x: world.allreduce(x, ops.SUM),
         lambda x: np.broadcast_to(x.sum(0), x.shape)[rows]),
        ("bcast_f32", f32, lambda x: world.bcast(x, root=0),
         lambda x: np.broadcast_to(x[0], x.shape)[rows]),
        ("allgather_f32", f32, world.allgather,
         lambda x: np.broadcast_to(x.reshape(-1), (n, x.size))[rows]),
    ]
    table = []
    # real sizes in a rehearsal too: this path is host-side either way
    _collective_sweep(c, put, SPANNING_BYTES, cases, table)

    # p2p of a device-resident payload across the process boundary
    pay = _ints(rng, (64 * MiB // 4,), np.float32)
    if first:
        world.send(jax.device_put(pay, local[0]), n - 1, tag=9, rank=0)
    if last:
        got, st = world.recv(source=0, tag=9, rank=n - 1)
        c.equal(got, pay, "cross-process send/recv")

    # RMA into a slot another process owns (fence epoch)
    slot = _ints(rng, (MiB // 4,), np.float32)
    win = win_allocate(world, slot.shape, np.float32)
    win.fence()
    if first:
        win.put(jax.device_put(slot, local[0]), n - 1)
    win.fence_end()
    if last:
        c.equal(np.asarray(win.read())[(n - 1) - off], slot,
                "cross-process win.put")
    world.barrier()
    win.free()

    # OSHMEM put across the boundary
    ctx = shmem.shmem_init(world)
    sym = ctx.malloc(slot.shape, np.float32)
    world.barrier()
    if first:
        ctx.put(sym, jax.device_put(slot + 1, local[0]), n - 1)
        ctx.quiet()
    world.barrier()
    if last:
        c.equal(ctx.get(sym, n - 1), slot + 1, "cross-process shmem put")
    world.barrier()

    pv = {}
    for name in NATIVE_PVARS:
        p = pvar.PVARS.lookup(name)
        pv[name] = None if p is None else p.read()
    if not pv["wire_native_frames"]:
        raise SystemExit(
            f"chip_smoke: rank {rank}: same-host peers moved no native "
            f"frames (the native wire datapath withdrew): {pv}")
    world.barrier()
    mpi.finalize()
    # which chip this process really holds: the device nodes it has
    # open (the shared /dev/vfio/vfio container node is not a chip)
    nodes = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            link = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if link.startswith(("/dev/accel", "/dev/vfio/")) \
                and link != "/dev/vfio/vfio":
            nodes.add(link)
    return c.finish(rank=rank, host_rank=rank in host_ranks,
                    chip=os.environ.get("TPU_VISIBLE_CHIPS"),
                    chip_nodes=sorted(nodes), world_size=n, pvars=pv,
                    table=table)


def leg_trainer(a) -> int:
    c = _Child(a)
    jax = c.jax
    import numpy as np
    import optax

    from ompi_release_tpu.models import transformer as tfm
    from ompi_release_tpu.parallel.mesh_axes import build_parallel_mesh

    devs = jax.devices()
    nd = len(devs)
    on_tpu = c.device["platform"] == "tpu"

    if c.rehearsal:  # toy widths, CPU rehearsal only
        base = dict(vocab=256, d_model=64, n_layers=2, n_heads=4,
                    head_dim=16, d_ff=128, max_seq=64)
        batch, seq = 8, 64
    else:
        base, batch, seq = {}, 8, 2048  # ModelConfig() defaults
    one = dict(dp=1, pp=1, sp=1, ep=1, tp=1)
    plans = [one] if nd == 1 else [dict(one, tp=2, dp=nd // 2)]
    if nd >= 4:
        plans.append(dict(one, sp=2, ep=2, dp=nd // 4))
    runs = []
    rng = np.random.default_rng(a.seed)
    for axes in plans:
        cfg = tfm.ModelConfig(
            n_experts=4 if axes["ep"] > 1 else 0, **base)
        mesh = build_parallel_mesh(devices=devs, **axes)
        params = tfm.shard_params(
            tfm.init_params(jax.random.PRNGKey(a.seed), cfg), cfg, mesh)
        opt = optax.adamw(1e-3)
        opt_state = jax.jit(opt.init)(params)
        tokens = rng.integers(0, cfg.vocab, size=(batch, seq),
                              dtype=np.int32)
        sh = tfm.make_batch_sharding(mesh)
        parity = None
        if axes["sp"] == 1:
            parity = _flash_vs_ring(c, tfm, cfg, mesh, params, sh,
                                    tokens[:max(2, axes["dp"])])
        before = c.pallas["calls"]
        step = tfm.make_train_step(cfg, mesh, opt)
        tok = jax.device_put(tokens, sh)
        tgt = jax.device_put(np.roll(tokens, -1, axis=1), sh)
        losses, step_s = [], []
        for _ in range(5):
            (params, opt_state, loss), dt = c.timed(
                lambda: step(params, opt_state, tok, tgt))
            losses.append(float(loss))
            step_s.append(dt)
        c.run_s += sum(step_s[1:])
        if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
            raise SystemExit(f"chip_smoke: trainer {axes}: losses {losses} "
                             "are not finite and falling")
        c.checks += 1
        # shards on every device, not only the right numbers
        c.on_devices(loss, devs, f"trainer {axes} loss")
        used = set()
        for leaf in jax.tree.leaves(params):
            used |= set(leaf.sharding.device_set)
        if used != set(devs):
            raise SystemExit(f"chip_smoke: trainer {axes}: params live on "
                             f"{len(used)} of {nd} devices")
        flash_built = c.pallas["calls"] > before
        if axes["sp"] == 1 and on_tpu and not flash_built:
            raise SystemExit(
                f"chip_smoke: trainer {axes}: attn_impl='auto' did not "
                "resolve to the Pallas flash kernel")
        runs.append({"axes": axes, "losses": losses, "step_s": step_s,
                     "attn": "flash" if flash_built else "ring",
                     "flash_vs_ring": parity})
    return c.finish(runs=runs)


def _flash_vs_ring(c, tfm, cfg, mesh, params, sh, tokens):
    """The Pallas flash kernels (forward, dq, dk/dv) at the widths they
    ship at, against ring attention through the same model: the loss
    and every parameter's gradient on a few full-length sequences (the
    reference keeps whole score matrices, so the batch is cut, nothing
    else). bf16 model, two attention algorithms: loss within 2e-3
    relative, each gradient leaf within 5e-2 of the reference's norm."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    jax = c.jax
    tok = jax.device_put(tokens, sh)
    tgt = jax.device_put(np.roll(tokens, -1, axis=1), sh)
    got = {}
    for impl in ("flash", "ring"):
        fwd = tfm.make_forward(dataclasses.replace(cfg, attn_impl=impl),
                               mesh)
        if c.rehearsal:
            # the interpreted kernel runs with shard_map's vma check
            # off, and the model does not differentiate that way: a
            # rehearsal compares the loss only
            got[impl] = (jax.block_until_ready(fwd(params, tok, tgt)), {})
        else:
            got[impl] = jax.block_until_ready(
                jax.jit(jax.value_and_grad(fwd))(params, tok, tgt))
    (lf, gf), (lr, gr) = got["flash"], got["ring"]
    loss_rel = abs(float(lf) - float(lr)) / abs(float(lr))
    grad_rel = {}
    for path, a in jax.tree_util.tree_leaves_with_path(gf):
        b = dict(jax.tree_util.tree_leaves_with_path(gr))[path]
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        grad_rel[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(a - b) / np.linalg.norm(b))
    worst = max(grad_rel, key=grad_rel.get, default="(loss only)")
    worst_rel = grad_rel.get(worst, 0.0)
    if not (np.isfinite(loss_rel) and loss_rel <= 2e-3
            and np.isfinite(worst_rel) and worst_rel <= 5e-2):
        raise SystemExit(
            f"chip_smoke: flash vs ring attention at {tokens.shape}: loss "
            f"{float(lf)} vs {float(lr)}, worst gradient {worst} off by "
            f"{worst_rel:.3g} of the reference's norm")
    c.checks += 1 + len(grad_rel)
    return {"tokens": list(tokens.shape), "loss_flash": float(lf),
            "loss_ring": float(lr), "loss_rel": loss_rel,
            "grad_rel_worst": worst_rel, "grad_worst_leaf": worst}


if __name__ == "__main__":
    sys.exit(main())
