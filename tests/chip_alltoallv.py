#!/usr/bin/env python3
"""Both bodies of the device-form alltoallv on NAS IS's counts, each
against the numpy model of ``test_vcoll_device.reference``.

    python tests/chip_alltoallv.py [--keys-log2 25] [--seed 1] [--bodies ragged,padded]

On the chips of one host (one process, ``mpi.init()`` world, one rank a
chip): every rank draws 2**keys_log2 IS keys (the benchmark's ``npb_is4``
configuration and its key draw), buckets and cuts them as NAS IS does, so
the send counts are NAS's, and the same exchange runs through the ragged
body (``lax.ragged_all_to_all``, what a TPU runs) and the padded body
(what every other backend runs). Prints one JSON line: per body whether
it matched and the median seconds of five calls after a warm-up; exit 0
only if every body matched and nothing was cut. XLA:CPU has no
ragged-all-to-all, so on the CPU only the padded body runs
(``test_vcoll_device.py`` calls :func:`run` so, at a small size).
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def run(world, keys_log2, seed, bodies):
    """{body: {"match", "median_s", "all_s"}} and the send counts, for
    one exchange of IS keys through each body named."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_release_tpu.coll import vcoll
    from perfbench import manifest
    from perfbench.ops import _npb
    from test_vcoll_device import reference

    n, m = world.size, 1 << keys_log2
    cfg = manifest.load_json(os.path.join(os.path.dirname(HERE), "perfbench",
                                          "configs", "npb_is4.json"))
    p = _npb.params(n, m, cfg)
    draw, bucket, cut, _ = _npb.programs(world.submesh, p)
    bits = jax.jit(lambda k: jax.random.bits(k, (n, m), "uint32"),
                   out_shardings=NamedSharding(world.submesh, P("rank")))(
        jax.random.key(seed))
    keys, local = bucket(draw(bits))
    send, _ = cut(local, world.allreduce(local))
    want, want_counts, dropped = reference(np.asarray(keys), np.asarray(send),
                                           p.cap)
    result = {"ranks": n, "keys_per_rank": m, "recv_capacity": p.cap,
              "send_counts": np.asarray(send).tolist(),
              "cut": dropped.tolist()}
    truncated = np.asarray(vcoll.truncated(world))
    for name in bodies:
        body = {"ragged": vcoll._ragged_body, "padded": vcoll._padded_body}[
            name]

        def once():
            t = time.perf_counter()
            out = vcoll._exchange(world, keys, send, p.cap, body)
            jax.block_until_ready(out)
            return out, time.perf_counter() - t

        once()
        times = []
        for _ in range(5):
            (recv, counts), dt = once()
            times.append(dt)
        result[name] = {
            "match": bool(np.array_equal(np.asarray(recv), want)
                          and np.array_equal(np.asarray(counts),
                                             want_counts)),
            "median_s": statistics.median(times), "all_s": times}
    result["truncated"] = (np.asarray(vcoll.truncated(world))
                           - truncated).tolist()
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys-log2", type=int, default=25)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--bodies", default="ragged,padded")
    a = ap.parse_args(argv)
    import jax

    import ompi_release_tpu as mpi

    world = mpi.init()
    bodies = a.bodies.split(",")
    result = dict(run(world, a.keys_log2, a.seed, bodies),
                  device=jax.devices()[0].device_kind)
    print(json.dumps(result), flush=True)
    mpi.finalize()
    ok = all(result[b]["match"] for b in bodies) and not any(
        result["cut"] + result["truncated"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
