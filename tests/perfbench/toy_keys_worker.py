"""A configuration that is not OSU's, added as data: the benchmark's worker
with one operation registered that is no file of ``perfbench/ops/`` and a
manifest that is a copy with that configuration appended.

The operation, ``toy_rank_keys``, is the local step of NAS IS: each rank
ranks its keys with a counting sort over ``[0, max_key)``, the range the
configuration states. Its ``make`` draws those keys as IS does, each the
mean of four uniforms scaled to ``max_key`` (a bell over the range), in
the harness's one jitted program before the first warm-up call. Its
``expected`` sorts each rank's share with numpy. Given uniform 32-bit keys
instead, the counting sort is wrong and the comparison says so.

Environment: ``PERFBENCH_TOY_ROOT`` is the copy's root (its
``BENCHMARK.json`` and ``perfbench/``); the trace goes under it too.
``PERFBENCH_TOY_MAKE=0`` takes ``make`` away, so the operation gets the
uniform keys of the general generator.
"""

import functools
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "toy_rank_keys"

KIND = "move"


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)


def make(key, n, elems, cfg):
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(key, (4, n, elems))
    return (u.mean(0) * cfg["max_key"]).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _sort(max_key):
    import jax
    import jax.numpy as jnp

    def one(row):
        hist = jnp.zeros(max_key, jnp.int32).at[row].add(1)
        return jnp.repeat(jnp.arange(max_key, dtype=jnp.int32), hist,
                          total_repeat_length=row.shape[0])

    return jax.jit(jax.vmap(one))


def call(world, x, cfg):
    return _sort(cfg["max_key"])(x)


def expected(x, cfg, sums):
    return (lambda r: np.sort(x[r])), None


def least_bytes(n, s):
    # the chip reads its keys and writes them sorted
    return 0, 2 * s


def main():
    sys.path.insert(0, ROOT)
    from perfbench import manifest, worker

    copy = os.environ["PERFBENCH_TOY_ROOT"]
    # this module is the operation, with or without ``make``
    mod = sys.modules[__name__]
    sys.modules[f"perfbench.ops.{NAME}"] = mod
    if os.environ.get("PERFBENCH_TOY_MAKE") == "0":
        del mod.make
    manifest.Manifest = functools.partial(
        manifest.Manifest, root=copy, bench=os.path.join(copy, "perfbench"))
    worker.ROOT = copy
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
