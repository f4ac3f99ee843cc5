"""OpenSHMEM between two ``tpurun`` ranks on the CPU, each process one PE
(ISSUE 36): seeded random lists of puts and gets at an offset and AMOs on
one word, issued by PE 0 and then by PE 1 against both PEs, agree exactly
with the numpy model of the symmetric heap (``shmem_reference.py``);
``my_pe`` is the caller's rank; ``wait_until`` on the own PE returns after
the peer's ``put`` + ``quiet`` and makes no wire request; ``local`` refuses
the peer's PE. Started as ``tests/perfbench/test_perfbench_rma.py`` starts
its job, with no limit tighter than that file's."""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

from ompi_release_tpu.tools.tpurun import Job

import shmem_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ELEMS, COUNT = 40, 90
LISTS = [("int32", 11), ("int32", 2**31 + 12), ("float32", 13)]

APP = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %r)
    sys.path.insert(0, %r)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    import ompi_release_tpu.osc.wire_win  # its counters
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.oshmem import shmem
    from ompi_release_tpu.utils.errors import ErrorCode, MPIError
    import shmem_reference as ref

    out_dir, ELEMS, COUNT = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    LISTS = json.loads(sys.argv[4])
    world = mpi.init()
    ctx = shmem.shmem_init(world)
    me = world.local_comm_ranks[0]
    doc = {"rank": me, "my_pe": ctx.my_pe, "n_pes": ctx.n_pes, "lists": []}

    def hexed(a):
        a = np.asarray(a)
        return [str(a.dtype), list(a.shape), a.tobytes().hex()]

    # each PE in turn issues its own seeded list against both PEs
    for dtype, seed in LISTS:
        sym = ctx.malloc((ELEMS // 4, 4), np.dtype(dtype))
        fetched = []
        for origin in (0, 1):
            if origin == me:
                ops = ref.random_ops(np.random.default_rng(seed + origin),
                                     2, ELEMS, COUNT, dtype)
                fetched = ref.play(ctx, sym, ops)
            ctx.barrier_all()
        doc["lists"].append({"fetched": [hexed(v) for v in fetched],
                             "mine": hexed(sym.local(me))})
        try:
            sym.local(1 - me)
            doc["local_of_the_peer"] = "returned"
        except MPIError as e:
            doc["local_of_the_peer"] = e.code.name
        sym.free()

    # a posted put, then a fetching AMO on the same word of the peer's PE
    word = ctx.malloc((8,), np.int32)
    flag = ctx.malloc((1,), np.int32)
    if me == 0:
        ctx.put(word, np.int32([41]), 1, offset=3)
        ctx.atomic_inc(word, 1, index=3)
        doc["fetched_behind_the_put"] = int(
            ctx.atomic_fetch_add(word, 10, 1, index=3))
    ctx.barrier_all()
    doc["word"] = hexed(word.local(me))

    # wait_until on the own PE: the peer puts and quiets; no wire request
    requests = pvar.PVARS.lookup("osc_wire_requests")
    if me == 1:
        before = requests.read()
        doc["test_before"] = ctx.test(flag, "eq", 7)
        got = ctx.wait_until(flag, "eq", 7)
        doc["waited_for"] = int(np.asarray(got)[0])
        doc["test_after"] = ctx.test(flag, "ne", 0)
        doc["requests_while_waiting"] = requests.read() - before
    else:
        time.sleep(0.3)
        ctx.put(flag, np.int32([7]), 1, offset=0)
        ctx.quiet()
    ctx.barrier_all()

    # host operands stay on the host (ISSUE 37): a drain of one put and 64
    # one-word AMOs, other operands each time, then 64 puts of device words
    AMOS = 64
    table = ctx.malloc((AMOS,), np.int32)
    host_payloads = pvar.PVARS.lookup("osc_host_payloads")
    plan_hits = pvar.PVARS.lookup("osc_plan_cache_hits")
    base = jax.device_put(np.arange(AMOS, dtype=np.int32))
    words = [jax.device_put(np.int32([1000 + j])) for j in range(AMOS)]
    doc["drains"] = []
    if me == 0:
        for rep in range(4):
            before = host_payloads.read(), plan_hits.read()
            if rep < 3:
                ctx.put(table, base, 1, offset=0)
                for j in range(AMOS):
                    ctx.atomic_add(table, 100 * rep + j, 1, index=j)
            else:
                for j, w in enumerate(words):
                    ctx.put(table, w, 1, offset=j)
            ctx.quiet()
            hits = plan_hits.read()
            doc["drains"].append(
                [host_payloads.read() - before[0],
                 hits["count"] - before[1]["count"],
                 hits["sum"] - before[1]["sum"]])
            doc.setdefault("table_seen", []).append(
                hexed(ctx.get(table, 1)))
    ctx.barrier_all()
    with open(os.path.join(out_dir, "rank%%d.json" %% me), "w") as f:
        json.dump(doc, f)
    shmem.shmem_finalize()
    world.barrier()
    mpi.finalize()
""") % (REPO, HERE)


@pytest.fixture(scope="module")
def ranks(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("shmem_two_ranks")
    app = tmp / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp), str(ELEMS), str(COUNT),
                  json.dumps(LISTS)], [], heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    docs = []
    for r in (0, 1):
        with open(tmp / f"rank{r}.json") as f:
            docs.append(json.load(f))
    return docs


def unhexed(entry):
    dtype, shape, data = entry
    return np.frombuffer(bytes.fromhex(data), dtype).reshape(shape)


@pytest.mark.parametrize("k", range(len(LISTS)),
                         ids=[f"{d}-{s}" for d, s in LISTS])
def test_both_origins_lists_agree_with_the_model(ranks, k):
    dtype, seed = LISTS[k]
    lists = [ref.random_ops(np.random.default_rng(seed + origin), 2, ELEMS,
                            COUNT, dtype) for origin in (0, 1)]
    want, heap = ref.run(2, ELEMS, dtype, lists[0] + lists[1])
    cut = len(ref.run(2, ELEMS, dtype, lists[0])[0])
    assert 0 < cut < len(want)  # both origins fetched something
    for doc, mine in zip(ranks, (want[:cut], want[cut:])):
        got = [unhexed(v) for v in doc["lists"][k]["fetched"]]
        assert len(got) == len(mine)
        for g, w in zip(got, mine):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(
            unhexed(doc["lists"][k]["mine"]).reshape(-1), heap[doc["rank"]])


def test_each_process_is_its_own_pe(ranks):
    assert [d["my_pe"] for d in ranks] == [0, 1]
    assert [d["n_pes"] for d in ranks] == [2, 2]
    assert [d["local_of_the_peer"] for d in ranks] == ["ERR_RMA_SHARED"] * 2


def test_a_fetching_amo_sees_the_put_posted_before_it(ranks):
    assert ranks[0]["fetched_behind_the_put"] == 42
    np.testing.assert_array_equal(unhexed(ranks[1]["word"]),
                                  [0, 0, 0, 52, 0, 0, 0, 0])
    assert not unhexed(ranks[0]["word"]).any()


def test_wait_until_on_the_own_pe_makes_no_wire_request(ranks):
    waiter = ranks[1]
    assert waiter["test_before"] is False and waiter["test_after"] is True
    assert waiter["waited_for"] == 7
    assert waiter["requests_while_waiting"] == 0


def test_a_drain_keeps_host_operands_on_the_host_and_its_plan(ranks):
    """Per drain of PE 0: [host payloads queued, batches through the
    template cache, of them replays]. 64 host operands a drain and none
    from the device puts; other VALUES each time replay the one frozen
    template (the first drain freezes it), and the home saw them."""
    assert ranks[0]["drains"] == [
        [64, 1, 0], [64, 1, 1], [64, 1, 1], [0, 1, 0]]
    assert ranks[1]["drains"] == []
    seen = [unhexed(v) for v in ranks[0]["table_seen"]]
    for rep in range(3):
        np.testing.assert_array_equal(
            seen[rep], np.arange(64) + 100 * rep + np.arange(64))
    np.testing.assert_array_equal(seen[3], 1000 + np.arange(64))
