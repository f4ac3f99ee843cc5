"""The benchmark's worker with the one-sided path broken underneath; the
harness is untouched. PERFBENCH_FAULT names the fault:

    dropped       the second of a call's 64 puts never happens: the slot
                  keeps what the call before left at that displacement
    swapped       the first two blocks of every call land at each other's
                  displacement: every byte arrives, two blocks in the wrong
                  place
    get_kept      ``get`` hands back this rank's OWN slot in the range that
                  was asked of the target's
    stale_flush   ``flush`` returns before the home has applied the call's
                  puts: they are held back and applied by the next call's
                  flush, so the target always reads a slot one call old
    no_counters   the library has no RMA wire counters, as the commit
                  before PR 34: the configuration's ``requires`` ends the
                  run; a window made all the same leaves a file behind
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.osc import wire_win
    from ompi_release_tpu.request.request import Request, Status

    Win = wire_win.WireWindow
    put, get, flush = Win.put, Win.get, Win.flush

    def dropped(self, data, target, index=None, disp=None):
        if disp is not None and disp == data.size:
            return None
        return put(self, data, target, index, disp)

    def swapped(self, data, target, index=None, disp=None):
        if disp in (0, data.size):
            disp = data.size - disp
        return put(self, data, target, index, disp)

    def get_kept(self, target, disp=None, count=None):
        req = Request()
        req.complete(value=self._data[0][disp:disp + count],
                     status=Status(source=target))
        return req

    def stale_flush(self, target):
        with self._op_lock:
            now, self._pending = self._pending, getattr(self, "_held", [])
            self._held = [p for p in now if p.kind == "put"]
            self._pending += [p for p in now if p.kind != "put"]
        return flush(self, target)

    if fault == "dropped":
        Win.put = dropped
    elif fault == "swapped":
        Win.put = swapped
    elif fault == "get_kept":
        Win.get = get_kept
    elif fault == "stale_flush":
        Win.flush = stale_flush
    elif fault == "no_counters":
        lookup, init = pvar.PVARS.lookup, Win.__init__
        pvar.PVARS.lookup = lambda name: (
            None if name in ("osc_wire_bytes", "osc_wire_ops")
            else lookup(name))

        def made(self, *a, **kw):
            open(os.environ["PERFBENCH_WINDOW_MADE"], "w").close()
            return init(self, *a, **kw)

        Win.__init__ = made
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
