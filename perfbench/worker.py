#!/usr/bin/env python3
"""The process that holds the chip(s): one cell, once.

Started by run.py, either directly (launcher ``driver``: one process,
``mpi.init()`` world over all local chips) or as the program of
``tpurun -n N`` (launcher ``tpurun``: one rank per process; a rank the
configuration lists under ``host_ranks`` declares itself one by setting
``JAX_PLATFORMS=cpu`` before jax is imported). It makes the data from the
seed, warms up exactly the cell's (operation, size) pairs, runs whole
rounds for the window, reads the pvars and the trace, compares what timed
calls returned with the numpy reference, and prints one marked JSON line
that run.py merges into the result.
"""

import argparse
import collections
import contextlib
import functools
import json
import math
import os
import shutil
import statistics
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MARK = "PERFBENCH-RANK "
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--t0", type=float, required=True,
                    help="epoch seconds at which run.py started")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also compare the lower-precision control")
    ap.add_argument("--extra-seeds", default="",
                    help="further seeds read in this process: one round each")
    return ap.parse_args(argv)


class Bench:
    """One cell in one process: the world, the data, the calls."""

    def __init__(self, a):
        sys.path.insert(0, ROOT)
        from perfbench import manifest, traffic

        self.a = a
        self.man = manifest.Manifest()
        self.cell = self.man.cell(a.workload, toy=a.rehearse_cpu)
        self.cfg = cfg = self.cell["config"]
        self.spanning = cfg["launcher"] == "tpurun"
        self.rank = (int(os.environ["OMPITPU_NODE_ID"]) - 1
                     if self.spanning else 0)
        self.host_rank = self.rank in cfg["host_ranks"]
        if self.host_rank or a.rehearse_cpu:
            # an EXPLICIT host rank: declared before jax is imported
            os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        import jax.monitoring

        self.jax = jax
        self.durations = collections.defaultdict(float)
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(
            lambda ev, **kw: self.events.update([ev]))
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        want = 1 if self.spanning else self.cell["chips"]
        if not a.rehearse_cpu and not self.host_rank and (
                devs[0].platform != "tpu" or len(devs) < want):
            raise SystemExit(
                f"perfbench: rank {self.rank} of {a.workload} needs {want} "
                f"TPU chip(s); jax came up on {len(devs)} x "
                f"{devs[0].platform!r}")

        import ompi_release_tpu as mpi
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ompi_release_tpu.runtime.runtime import Runtime

        self.mpi = mpi
        self.world = world = mpi.init()
        rt = Runtime.current()
        if world.size != cfg["ranks"]:
            raise SystemExit(f"perfbench: {cfg['name']} is {cfg['ranks']} "
                             f"ranks, the world has {world.size}")
        if self.spanning and not (rt.unified and world.spans_processes):
            raise SystemExit("perfbench: tpurun did not form a unified world")
        self.n = world.size
        self.off = rt.local_rank_offset if self.spanning else 0
        self.local_n = rt.local_size if self.spanning else self.n
        self.devices = list(world.submesh.devices.flat)
        self.sharding = NamedSharding(world.submesh, P("rank"))
        self.round = traffic.round_of(self.cell)
        self.keys = list(traffic.inputs_of(self.cell))
        self.ops = {op: manifest.operation(op) for op, _ in self.round}
        self.compiles = 0
        self.raised = 0

    def _duration(self, event, seconds, **kw):
        self.durations[event] += seconds
        if event == COMPILE_EVENT:
            self.compiles += 1

    # -- data ---------------------------------------------------------------
    def make_data(self, seed):
        """The cell's inputs from the seed, on the device. A spanning
        rank draws all n rows (the reference needs them) and sends its
        own."""
        from perfbench import traffic

        jax = self.jax
        self.full = traffic.make_inputs(
            seed, self.keys, self.n, None if self.spanning else self.sharding,
            self.cfg)
        if self.spanning:
            rows = slice(self.off, self.off + self.local_n)
            self.local = {k: jax.device_put(v[rows], self.sharding)
                          for k, v in self.full.items()}
        else:
            self.local = self.full
        jax.block_until_ready(list(self.local.values()))

    # -- one call -------------------------------------------------------------
    def call(self, op, size):
        from perfbench import traffic

        x = self.local[traffic.input_key(self.cell, op, size)]
        t = time.perf_counter()
        out = self.ops[op].call(self.world, x, self.cfg)
        self.jax.block_until_ready(out)
        return out, time.perf_counter() - t

    def agree(self, *values):
        """Rank 0's whole numbers on every rank (one small bcast, before
        the window)."""
        if not self.spanning:
            return values
        import numpy as np

        x = np.tile(np.asarray(values, np.int32), (self.local_n, 1))
        out = self.world.bcast(self.jax.device_put(x, self.sharding), root=0)
        return tuple(int(v) for v in np.asarray(out)[0])

    # -- the window -----------------------------------------------------------
    def window(self, seconds, rounds, slice_rounds, keep, trace_dir):
        """Whole rounds: until ``seconds`` are spent, or ``rounds`` of
        them where the ranks had to agree beforehand. Of each (operation,
        size) one call's result is kept for the comparison, drawn from
        ``keep`` (a reservoir, so each call of the window is as likely).
        With ``slice_rounds`` the profiler covers that many rounds from
        the second on. Returns the window's facts."""
        jax = self.jax
        from perfbench import trace, traffic

        times, kept, payload, done = [], {}, 0, 0
        by_call, ends = collections.defaultdict(list), []
        first, last = (1, 1 + slice_rounds) if slice_rounds else (-1, -1)
        sl = None
        span = contextlib.ExitStack()
        t0 = time.perf_counter()
        while True:
            if done == first and trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                span.enter_context(jax.profiler.TraceAnnotation(trace.SLICE))
            if done == first:
                sl = {"t0": time.perf_counter(), "calls": 0}
            in_slice = first <= done < last
            for op, size in self.round:
                note = (jax.profiler.TraceAnnotation(
                    f"{trace.CALL}{op}:{size}") if in_slice and trace_dir
                    else contextlib.nullcontext())
                try:
                    with note:
                        out, dt = self.call(op, size)
                except Exception:  # the call failed: counted, and compared as missing
                    traceback.print_exc()
                    self.raised += 1
                    continue
                times.append(dt)
                by_call[f"{op}:{size}"].append(dt)
                payload += traffic.payload_bytes(self.cell, op, size)
                if keep.random() * (done + 1) < 1.0:
                    kept[(op, size)] = out
                del out
                if in_slice:
                    sl["calls"] += 1
            done += 1
            ends.append(time.perf_counter())
            if done == last:
                sl["seconds"] = time.perf_counter() - sl["t0"]
                span.close()
                if trace_dir:
                    jax.profiler.stop_trace()
            if done >= last and (done >= rounds if rounds else
                                 time.perf_counter() - t0 >= seconds):
                break
        return {"seconds": time.perf_counter() - t0, "calls": len(times),
                "call_seconds": times, "payload_bytes": payload,
                "rounds": done, "slice": sl,
                "round_seconds": [b - a for a, b in zip([t0] + ends, ends)],
                "by_call": by_call}, kept

    # -- the comparison ---------------------------------------------------------
    def rows_of(self, out):
        """(global rank, a call that fetches that rank's buffer) for each
        local rank; a shard is fetched once, when its first row is
        asked for."""
        import numpy as np

        for shard in out.addressable_shards:
            fetch = functools.lru_cache(1)(lambda s=shard: np.asarray(s.data))
            start = shard.index[0].start or 0
            for i in range(shard.data.shape[0]):
                yield self.off + start + i, (lambda f=fetch, i=i: f()[i])

    def compare(self, kept, control=False):
        """The kept results against the reference: the cell's numbers
        (the worst over its operations and sizes) and one line per
        (operation, size)."""
        import numpy as np

        from perfbench import reference, traffic

        jax, detail, wrong = self.jax, [], self.raised
        host, sums, lim = {}, reference.Sums(), reference.limits()
        num = {"sum_err_ulp": None, "moved_mismatch": 0, "misplaced": 0,
               "missing": self.raised}
        has_move = any(m.KIND == "move" for m in self.ops.values())
        for op, size in self.round:
            key = traffic.input_key(self.cell, op, size)
            out = kept.get((op, size))
            if out is None:
                num["missing"] += 1
                wrong += 1
                detail.append(f"{op}:{size} no result")
                continue
            if (not isinstance(out, jax.Array)
                    or set(out.sharding.device_set) != set(self.devices)
                    or out.shape[0] != self.local_n):
                num["misplaced"] += 1
                wrong += 1
                detail.append(f"{op}:{size} misplaced: {type(out).__name__} "
                              f"{getattr(out, 'shape', None)}")
                continue
            if key not in host:
                host[key] = np.asarray(self.full[key])
            got = reference.compare(op, self.cfg, host[key],
                                    self.rows_of(out), sums, control)
            if self.ops[op].KIND == "reduce":
                num["sum_err_ulp"] = max(num["sum_err_ulp"] or 0.0,
                                         got["err_ulp"])
                wrong += not got["err_ulp"] <= lim["sum_err_ulp"]
            else:
                num["moved_mismatch"] += got["mismatch"]
                wrong += got["mismatch"] > lim["moved_mismatch"]
            detail.append(f"{op}:{size} err_ulp={got['err_ulp']} "
                          f"mismatch={got['mismatch']}/{got['elements']}")
        if not has_move:
            num["moved_mismatch"] = None
        return num, detail, int(wrong)

    def timed_round(self):
        t = time.perf_counter()
        self.one_round()
        return time.perf_counter() - t

    def one_round(self):
        kept = {}
        for op, size in self.round:
            kept[(op, size)], _ = self.call(op, size)
        return kept


def read_trace(trace_dir, host_seconds, rehearsal=False):
    """The traced slice reduced, with the host spans; a trace with no
    device operations ends the run with what it did hold. Outside a
    rehearsal only a ``/device:TPU:<k>`` plane counts as a device."""
    from perfbench import trace

    devices, spans, seen = trace.load(trace.newest_xplane(trace_dir),
                                      rehearsal)
    try:
        return trace.reduce(devices, spans, host_seconds), spans
    except trace.NoDevicePlane as e:
        raise SystemExit(f"perfbench: {e}; the trace holds: "
                         f"{trace.describe(seen)}")


def spread(values):
    """Every value where they are few, else [least, quartiles, most]."""
    if len(values) <= 32:
        return values
    q = statistics.quantiles(values, n=4)
    return [min(values), *q, max(values)]


def snapshot(names):
    from ompi_release_tpu.mca import pvar

    out = {}
    for name in names:
        p = pvar.PVARS.lookup(name)
        if p is not None:
            v = p.read()
            out[name] = dict(v) if isinstance(v, dict) else v
    return out


def delta(after, before):
    out = {}
    for name, v in after.items():
        b = before.get(name, 0)
        if isinstance(v, dict):
            out[name] = {k: v[k] - (b[k] if isinstance(b, dict) else 0)
                         for k in ("sum", "count")}
        else:
            out[name] = v - b
    return out


def main(argv=None):
    a = parse(argv)
    reached = {}  # seconds since run.py started, at the end of each stage

    def stage(name):
        reached[name] = time.time() - a.t0

    b = Bench(a)
    jax = b.jax
    stage("init")  # python and jax imported, the chip reached, mpi.init
    import numpy as np

    from perfbench import least, manifest, traffic

    b.make_data(a.seed)
    stage("data")
    # warm up exactly this cell's (operation, size) pairs, twice each: the
    # first call compiles and freezes the plan, the second replays it
    for k in (1, 2):
        for op, size in b.round:
            b.call(op, size)
        stage(f"warm_up_{k}")
    # how long a round takes, to fix the rounds where the ranks must agree
    # and to size the traced slice: one timed round, and where rounds are
    # short as many more as fit in a second (rank 0 says how many)
    took = [b.timed_round()]
    for _ in range(*b.agree(min(20, int(1.0 // took[0])))):
        took.append(b.timed_round())
    t_round = statistics.median(took)
    tr = b.cell["traffic"].get("slice", {})
    slice_rounds = 0
    if a.trace:
        slice_rounds = min(tr.get("rounds_max", 1 << 30), max(
            tr.get("rounds_min", 1),
            math.ceil(tr.get("seconds", 2.0) / t_round)))
    rounds = 0
    if b.spanning:  # every rank must make the same calls: fixed beforehand
        rounds = max(1 + slice_rounds, math.ceil(a.seconds / t_round))
    rounds, slice_rounds = b.agree(rounds, slice_rounds)
    trace_dir = None
    if a.trace and b.rank == 0:
        trace_dir = os.path.join(ROOT, "perfbench_out", "trace", a.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)

    pvars = b.man.pvars_of(a.workload) + ["coll_programs_compiled"]
    before, compiles0 = snapshot(pvars), b.compiles
    setup_s = time.time() - a.t0
    facts, kept = b.window(a.seconds, rounds, slice_rounds,
                           np.random.default_rng(a.seed), trace_dir)
    facts["pvars"] = delta(snapshot(pvars), before)
    window_compiles = (b.compiles - compiles0
                       + facts["pvars"].pop("coll_programs_compiled", 0))
    facts["setup_s"] = setup_s
    stats = [d.memory_stats() or {} for d in b.devices]
    b.device["memory_peak_bytes"] = max(
        s.get("peak_bytes_in_use", 0) for s in stats)
    if b.spanning:
        b.device["count"] = len(b.devices)

    result = {"rank": b.rank, "host_rank": b.host_rank, "device": b.device,
              "attempted": facts["calls"] + b.raised, "rounds": facts["rounds"],
              "window_s": facts["seconds"], "round_s_warm": t_round,
              "compile_s": b.durations[COMPILE_EVENT],
              "setup_reached_s": dict(reached, window=setup_s),
              "cache_hits": b.events[CACHE_HIT_EVENT],
              # where a run reads far off, these say which rounds and calls
              "round_s": spread(facts["round_seconds"]),
              "by_call_ms": {k: statistics.median(v) * 1e3
                             for k, v in facts["by_call"].items()}}
    if b.rank == 0:
        sl = facts["slice"]
        if trace_dir:
            red, spans = read_trace(trace_dir, sl["seconds"], a.rehearse_cpu)
            kind = (b.device["kind"] if not a.rehearse_cpu
                    else "TPU v5 lite")  # rehearsal: arithmetic only
            peaks = manifest.peaks(kind)
            per_round = sum(least.seconds(op, b.n, traffic.payload_bytes(
                b.cell, op, size), peaks, b.cfg["interconnect"])
                for op, size in b.round)
            sl.update(window_s=red["window_s"], busy_s=red["busy_s"],
                      least_s=per_round * slice_rounds, spans=spans)
            b.device.update(window_s=red["window_s"], busy_s=red["busy_s"])
            result["breakdown"] = red["breakdown"]
            result["trace"] = {k: red[k] for k in
                               ("busiest_device", "devices", "calls_in_slice")}
            result["trace"]["host_slice_s"] = sl["seconds"]
        else:
            facts["slice"] = None
        group = "per_layer" if a.trace else "end_to_end"
        result["metrics"] = manifest.read_metrics(
            b.man.metrics_of(a.workload, group), facts)

    # how much of the peak is the harness's own: the results it holds for
    # the comparison (a caller's receive buffers), on the fullest device
    result["kept_bytes"] = max(
        sum(s.data.nbytes for out in kept.values()
            for s in getattr(out, "addressable_shards", ())
            if s.device == d) for d in b.devices)
    # the window has closed and the peak is read: now the reference
    t = time.perf_counter()
    num, result["detail"], result["failed"] = b.compare(kept)
    num["window_compiles"] = window_compiles
    result["numbers"] = num
    if a.control:  # the reference in lower precision, in the program's place
        result["control"] = b.compare(kept, control=True)[0]
    del kept
    result["compare_s"] = time.perf_counter() - t
    more = []
    for seed in (int(s) for s in a.extra_seeds.split(",") if s):
        b.make_data(seed)
        kept = b.one_round()
        entry = {"seed": seed, "numbers": b.compare(kept)[0]}
        if a.control:
            entry["control"] = b.compare(kept, control=True)[0]
        more.append(entry)
        del kept
    if more:
        result["extra_seeds"] = more
    if b.spanning:
        b.world.barrier()
    b.mpi.finalize()
    print(MARK + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
