"""The benchmark's worker with the timed path broken underneath: the
library's entry points are patched before the worker runs, the harness
is untouched. PERFBENCH_FAULT names the fault:

    no_exchange   allreduce returns its input: the exchange between the
                  ranks is left out
    altered       bcast delivers one element changed on the last local rank
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    # a rehearsal: run.py has put every rank on the CPU backend already
    from ompi_release_tpu.comm.communicator import Communicator

    if fault == "no_exchange":
        Communicator.allreduce = lambda self, x, op=None, **kw: x
    elif fault == "altered":
        sound = Communicator.bcast

        def bcast(self, x, root=0, **kw):
            out = sound(self, x, root, **kw)
            return out.at[-1, 0].add(1) if out.size > 2 else out

        Communicator.bcast = bcast
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
