"""The benchmark's worker with the OpenSHMEM path broken underneath; the
harness and the operations are untouched. PERFBENCH_FAULT names the fault:

    amo_dropped      the last of a call's 64 posted AMOs (an ``atomic_inc``)
                     never happens
    amo_twice        that AMO is applied twice
    amo_before_put   that AMO is applied BEFORE the put of the table it was
                     issued behind: the put then overwrites what it added
    fetch_new        a fetching add (``atomic_fetch_add``, so
                     ``atomic_fetch_inc`` too) returns the word's NEW value
    early_quiet      ``quiet`` returns before the home has applied what it
                     drained: the operations are held back and applied by
                     the allocation's next drain, so the partner always
                     reads an allocation one call old
    put_neighbour    the first two of a call's 64 blocks land at each
                     other's offset: every byte arrives, two blocks in the
                     neighbouring place
    get_own          ``get`` hands back the CALLER's own allocation in the
                     range that was asked of the partner's
    no_counters      the library has no OpenSHMEM counters, as the commit
                     before PR 36: the configuration's ``requires`` ends
                     the run; an allocation made all the same leaves a
                     file behind
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

POSTED = 64  # AMOs a call of oshm_amo_post posts


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.ops.op import SUM
    from ompi_release_tpu.osc import wire_win
    from ompi_release_tpu.oshmem.shmem import ShmemCtx
    from ompi_release_tpu.request.request import Request, Status

    Win = wire_win.WireWindow
    put, flush_all = Win.put, Win.flush_all
    accumulate, fetch_add = Win.accumulate, ShmemCtx.atomic_fetch_add
    posted = [0]

    def last_of_the_call():
        posted[0] += 1
        return posted[0] % POSTED == 0

    def amo_dropped(self, data, target, op=SUM, index=None, disp=None):
        if last_of_the_call():
            return None
        return accumulate(self, data, target, op, index, disp)

    def amo_twice(self, data, target, op=SUM, index=None, disp=None):
        if last_of_the_call():
            accumulate(self, data, target, op, index, disp)
        return accumulate(self, data, target, op, index, disp)

    def amo_before_put(self):
        with self._op_lock:
            p = self._pending
            if len(p) > 1 and p[0].kind == "put" and p[-1].kind == "acc":
                self._pending = [p[-1]] + p[:-1]
        return flush_all(self)

    def fetch_new(self, sym, value, pe, index=None):
        return fetch_add(self, sym, value, pe, index) + value

    def early_quiet(self):
        with self._op_lock:
            now, self._pending = self._pending, getattr(self, "_held", [])
            self._held = now
        return flush_all(self)

    def put_neighbour(self, data, target, index=None, disp=None):
        if (disp in (0, data.size)
                and data.size * POSTED == self._slot_elems()):
            disp = data.size - disp
        return put(self, data, target, index, disp)

    def get_own(self, target, disp=None, count=None):
        req = Request()
        req.complete(value=self._data[0].reshape(-1)[disp:disp + count],
                     status=Status(source=target))
        return req

    if fault == "amo_dropped":
        Win.accumulate = amo_dropped
    elif fault == "amo_twice":
        Win.accumulate = amo_twice
    elif fault == "amo_before_put":
        Win.flush_all = amo_before_put
    elif fault == "fetch_new":
        ShmemCtx.atomic_fetch_add = fetch_new
    elif fault == "early_quiet":
        Win.flush_all = early_quiet
    elif fault == "put_neighbour":
        Win.put = put_neighbour
    elif fault == "get_own":
        Win.get = get_own
    elif fault == "no_counters":
        lookup, init = pvar.PVARS.lookup, Win.__init__
        pvar.PVARS.lookup = lambda name: (
            None if name in ("shmem_blocking_ops", "shmem_quiets")
            else lookup(name))

        def made(self, *a, **kw):
            open(os.environ["PERFBENCH_ALLOCATION_MADE"], "w").close()
            return init(self, *a, **kw)

        Win.__init__ = made
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
