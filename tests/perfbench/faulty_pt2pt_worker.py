"""The benchmark's worker with the point-to-point path broken underneath;
the harness is untouched. PERFBENCH_FAULT names the fault:

    swapped         the first two messages of every window are delivered
                    swapped: every byte arrives, MPI's order does not hold
    altered_host    the first message of every window the HOST rank
                    receives (``recv``, or ``irecv`` and ``wait_all``) has
                    one element altered; the chip rank is sound, so only
                    each rank's own comparison can see it
    recv_kept       ``recv`` hands back (as much as arrived of) the buffer
                    this rank sent last instead of the one that arrived
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    from ompi_release_tpu import request
    from ompi_release_tpu.comm.communicator import Communicator

    recv, send, wait_all = Communicator.recv, Communicator.send, request.wait_all
    isend = Communicator.isend
    host_rank = int(os.environ["OMPITPU_NODE_ID"]) == 2

    def swapped(reqs):
        out = wait_all(reqs)
        got = [r for r in reqs if r.value is not None]
        if len(got) >= 2:
            got[0].value, got[1].value = got[1].value, got[0].value
        return out

    def altered(self, source=-1, tag=-1, *, rank):
        value, status = recv(self, source, tag, rank=rank)
        return (value.at[0, 0].add(1) if host_rank else value), status

    def altered_window(reqs):
        out = wait_all(reqs)
        got = [r for r in reqs if r.value is not None]
        if host_rank and got:
            got[0].value = got[0].value.at[0, 0].add(1)
        return out

    sent = []

    def remember(self, data, dest, tag=0, *, rank, **kw):
        sent[:] = [data]
        return send(self, data, dest, tag, rank=rank, **kw)

    def iremember(self, data, dest, tag=0, *, rank, **kw):
        sent[:] = [data]
        return isend(self, data, dest, tag, rank=rank, **kw)

    def kept(self, source=-1, tag=-1, *, rank):
        value, status = recv(self, source, tag, rank=rank)
        return (sent[0][:, :value.shape[1]] if sent else value), status

    if fault == "swapped":
        request.wait_all = swapped
    elif fault == "altered_host":
        Communicator.recv, request.wait_all = altered, altered_window
    elif fault == "recv_kept":
        Communicator.send, Communicator.isend = remember, iremember
        Communicator.recv = kept
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
