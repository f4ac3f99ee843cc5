#!/usr/bin/env python3
"""perfbench — one cell of BENCHMARK.json, once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax (a chip belongs to one process at a time).
It starts the cell's worker(s) — one process that holds every chip, or
``tpurun -n N`` with the worker as its program — collects what each rank
reports, and prints the result as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown",] "compared"}

``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (the profiler covers a slice of whole rounds). With no
TPU, with fewer chips than the cell asks for, or alone in a directory,
it exits non-zero and prints no result.

``--rehearse-cpu`` is a rehearsal on virtual CPU devices for debugging
the harness; its line is labelled and is never a chip result.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "worker.py")
LIMIT_S = 340.0  # a run ends within 360 s; a first run that compiles may take longer
# tpurun's failure detector ends the job when a rank's beats stay away for
# four intervals: 2 s at its default of 0.5 s, which a host that stands still
# for "some seconds" passes (PERF.md section 2). Its hangs are LIMIT_S's.
HEARTBEAT_S = 10.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="CPU rehearsal (labelled; never a chip result)")
    ap.add_argument("--control", action="store_true",
                    help="also compare the lower-precision control")
    ap.add_argument("--extra-seeds", default="",
                    help="further seeds compared in the same process")
    return ap.parse_args(argv)


def child_env(cfg, rehearse):
    """The workers' environment: the compile cache at a fixed path inside
    the checkout unless the machine names one, and for a rehearsal the
    virtual CPU devices."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    # jax persists only compiles of 1 s or more; the small programs are many,
    # and keeping them too cut a warm setup_s from 19.6 to 15.3 s (PERF.md)
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        if cfg["launcher"] == "driver":
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{cfg['ranks']}")
    return env


def launch(argv, env, limit_s):
    """One child, in a session of its own, to its end or the limit;
    whatever it started is ended with it. Returns (exit code, stdout)."""
    proc = subprocess.Popen(argv, cwd=ROOT, env=env, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=limit_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        print(f"perfbench: killed at {limit_s:.0f} s", file=sys.stderr)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    return proc.returncode, out


def judge(reports, reference):
    """Every rank's numbers as one comparison: the worst of each, beside
    its limit, and whether all keep to theirs."""
    numbers = {}
    for numbers_of_rank in reports:
        for k, v in numbers_of_rank.items():
            if v is not None:
                numbers[k] = max(numbers.get(k, 0), v)
    return reference.verdict(numbers)


def job_device(ranks, rehearsal=False):
    """The device of the JOB, not of rank 0: the chips of every rank that
    is not a declared host rank, together, and the fullest of them. The
    platform and the kind are rank 0's, and so are ``window_s`` and
    ``busy_s`` of a traced run: only the process that holds a chip can
    trace it, and rank 0's is the one traced. Outside a rehearsal every
    such rank has to be on a TPU, not rank 0 alone."""
    chip = [r for r in ranks if not r["host_rank"]]
    off = [r["rank"] for r in chip if r["device"]["platform"] != "tpu"]
    if off and not rehearsal:
        raise SystemExit(f"perfbench: FAILED: rank(s) {off} are not on a TPU "
                         "and are not declared host ranks")
    device = dict(ranks[0]["device"])
    device["count"] = sum(r["device"]["count"] for r in chip)
    device["memory_peak_bytes"] = max(r["device"]["memory_peak_bytes"]
                                      for r in chip)
    return device


def merge(ranks, trace, reference, rehearsal=False):
    """The ranks' reports as the one result. Rank 0 times and traces: the
    metrics are its; the device is the job's; every rank's comparison
    counts."""
    first = ranks[0]
    compared, ok = judge([r["numbers"] for r in ranks], reference)
    failed = sum(r["failed"] for r in ranks)
    result = {"correct": ok, "attempted": first["attempted"], "failed": failed,
              "metrics": first["metrics"],
              "device": job_device(ranks, rehearsal)}
    if trace and "breakdown" in first:
        result["breakdown"] = first["breakdown"]
    return result, compared


def controls(ranks, reference):
    """``--control`` and ``--extra-seeds``: each further comparison goes
    through the same verdict as the run's own, so a control is SEEN to come
    out not correct, not only printed beside the limit."""
    out = {}
    if all("control" in r for r in ranks):
        out["control"], out["control_correct"] = judge(
            [r["control"] for r in ranks], reference)
    extra = []
    for entries in zip(*(r.get("extra_seeds", []) for r in ranks)):
        entry = {"seed": entries[0]["seed"]}
        entry["compared"], entry["correct"] = judge(
            [e["numbers"] for e in entries], reference)
        if all("control" in e for e in entries):
            entry["control"], entry["control_correct"] = judge(
                [e["control"] for e in entries], reference)
        extra.append(entry)
    if extra:
        out["extra_seeds"] = extra
    return out


def main(argv=None, worker=WORKER):
    a = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "ompi_release_tpu")):
        print("perfbench: the ompi_release_tpu package is not next to this "
              "directory: nothing to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from perfbench import manifest, reference

    man = manifest.Manifest()
    cell = man.cell(a.workload)
    cfg = cell["config"]
    args = [sys.executable, worker, "--workload", a.workload,
            "--seed", str(a.seed), "--seconds", str(a.seconds),
            "--trace", str(a.trace), "--t0", repr(T0)]
    if a.rehearse_cpu:
        args.append("--rehearse-cpu")
    if a.control:
        args.append("--control")
    if a.extra_seeds:
        args += ["--extra-seeds", a.extra_seeds]
    if cfg["launcher"] == "tpurun":
        args = [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
                "-n", str(cfg["ranks"]), "--timeout", str(LIMIT_S),
                "--heartbeat", str(HEARTBEAT_S)] + args
    elif cfg["launcher"] != "driver":
        raise SystemExit(f"perfbench: launcher {cfg['launcher']!r} is not known")
    rc, out = launch(args, child_env(cfg, a.rehearse_cpu), LIMIT_S + 20)
    mark = "PERFBENCH-RANK "
    ranks = sorted((json.loads(ln.split(mark, 1)[1])
                    for ln in out.splitlines() if mark in ln),
                   key=lambda r: r["rank"])
    if rc != 0 or len(ranks) != (cfg["ranks"] if cfg["launcher"] == "tpurun"
                                 else 1):
        sys.stderr.write(out[-6000:])
        print(f"perfbench: FAILED: exit code {rc}, {len(ranks)} rank(s) "
              "reported", file=sys.stderr)
        return 1
    result, compared = merge(ranks, a.trace, reference, a.rehearse_cpu)
    if a.rehearse_cpu:
        result["rehearsal"] = "cpu: NOT a chip result"
    result.update(controls(ranks, reference))
    result["run"] = {
        "workload": a.workload, "seed": a.seed, "trace": a.trace,
        "ranks": [{k: r.get(k) for k in (
            "rank", "host_rank", "rounds", "window_s", "round_s_warm",
            "compile_s", "setup_reached_s", "cache_hits", "compare_s",
            "kept_bytes", "trace",
            "round_s", "by_call_ms")} for r in ranks]}
    result["compared"] = compared  # last in the line
    for r in ranks:
        for ln in r["detail"]:
            print(f"perfbench rank {r['rank']} {ln}", file=sys.stderr)
    for key in ("control_correct",):
        if key in result:
            print(f"perfbench {key} = {json.dumps(result[key])}",
                  file=sys.stderr)
    print("\n".join(reference.lines(compared)), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
