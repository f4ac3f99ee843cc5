"""The benchmark's worker with the data-movement path broken underneath;
the harness is untouched. PERFBENCH_FAULT names the fault:

    altered_last_rank   bcast delivers one element changed on the LAST rank
                        alone, every other rank is sound: rank 0's
                        comparison cannot see it, only each rank's own can
    bcast_kept          bcast returns its input on every rank: the exchange
                        between the ranks is left out

Both spare the harness's own bcast of one or two whole numbers.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    from ompi_release_tpu.comm.communicator import Communicator

    sound = Communicator.bcast

    def altered(self, x, root=0, **kw):
        out = sound(self, x, root, **kw)
        last = int(os.environ["OMPITPU_NODE_ID"]) == self.size
        return out.at[-1, 0].add(1) if last and out.size > 2 else out

    if fault == "altered_last_rank":
        Communicator.bcast = altered
    elif fault == "bcast_kept":
        # the harness's own two-number bcast (``Bench.agree``) stays sound,
        # or the ranks would not make the same calls
        Communicator.bcast = lambda self, x, root=0, **kw: (
            x if x.size > 2 else sound(self, x, root, **kw))
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
