"""ESS — environment-specific bootstrap (``orte/mca/ess`` analogue).

How does this process learn its identity and device set? The reference
has one component per launch environment (env/singleton/pmi/slurm...,
``orte/mca/ess/``). Here:

  - ``singleton``: one controller process owning all locally-visible
    devices (the common JAX case; ``ess/singleton`` analogue).
  - ``distributed``: multi-controller via ``jax.distributed`` —
    coordinator address/rank from env (the ``ess/env``+``ess/pmi``
    analogue; the jax coordinator service replaces the orted tree).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

from ..mca import component as mca_component
from ..mca import var as mca_var
from ..utils import output

_log = output.stream("ess")


def read_stdin_secret(stream) -> str:
    """One line of ``stream`` as the job secret (OMPITPU_SECRET_STDIN
    rsh handoff). An empty line / EOF means the launcher died or the
    pipe was misplumbed — that MUST fail the launch loudly: silently
    proceeding would disable auth on this endpoint and surface later
    as an inexplicable connect hang against the authenticated HNP."""
    from ..utils.errors import ErrorCode, MPIError

    secret = stream.readline().strip()
    if not secret:
        raise MPIError(
            ErrorCode.ERR_OTHER,
            "OMPITPU_SECRET_STDIN=1 but stdin closed before a job "
            "secret arrived (launcher died, or the rsh pipe was not "
            "plumbed) — refusing to start with auth silently disabled",
        )
    return secret


class SingletonEss(mca_component.Component):
    """Single-controller bootstrap: all visible devices, process 0."""

    NAME = "singleton"
    PRIORITY = 10

    def bootstrap(self):
        import jax

        return {
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "devices": jax.devices(),
            "local_devices": jax.local_devices(),
        }


class DistributedEss(mca_component.Component):
    """Multi-host bootstrap through the jax.distributed coordinator.

    Selected when coordinator env vars are present (the analogue of
    ess/env detecting mpirun's environment variables).
    """

    NAME = "distributed"
    PRIORITY = 50

    def register_vars(self) -> None:
        mca_var.register(
            "ess_distributed_coordinator", "str",
            os.environ.get("OMPITPU_COORDINATOR", ""),
            "host:port of the jax.distributed coordinator service",
        )
        mca_var.register(
            "ess_distributed_process_id", "int",
            int(os.environ.get("OMPITPU_PROCESS_ID", "-1")),
            "this controller's process id within the job (-1 = unset)",
        )
        mca_var.register(
            "ess_distributed_num_processes", "int",
            int(os.environ.get("OMPITPU_NUM_PROCESSES", "0")),
            "total controller processes in the job",
        )

    def query(self, ctx=None):
        if not mca_var.get("ess_distributed_coordinator"):
            return None  # not launched under a coordinator
        return (self.priority, self)

    def bootstrap(self):
        import jax

        coord = mca_var.get("ess_distributed_coordinator")
        pid = mca_var.get("ess_distributed_process_id")
        nprocs = mca_var.get("ess_distributed_num_processes")
        _log.verbose(1, f"jax.distributed.initialize({coord}, {nprocs}, {pid})")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nprocs if nprocs > 0 else None,
            process_id=pid if pid >= 0 else None,
        )
        return {
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "devices": jax.devices(),
            "local_devices": jax.local_devices(),
        }


def host_platform_declared() -> bool:
    """True when this process was started with an explicit
    ``JAX_PLATFORMS`` that leaves the TPU out (``cpu``): a CPU run that
    was asked for by name, as opposed to jax's silent fallback when no
    chip answers."""
    import jax

    pinned = str(jax.config.jax_platforms or "")
    return bool(pinned) and "tpu" not in pinned.split(",")


def _bound_platform() -> str:
    """Platform of this worker's devices, holding the launcher's chip
    binding to its word: ``tpurun`` gave this process one local slot
    = one chip (``tools/tpurun.chip_binding_env``). If no chip answers
    for the slot the rank FAILS here, naming the slot — it never
    quietly becomes a CPU rank (jax falls back to the CPU without a
    word when ``JAX_PLATFORMS`` is unset). A host rank is one that was
    started with an explicit ``JAX_PLATFORMS`` that leaves the TPU
    out; those behave as they always did."""
    import jax

    from ..utils.errors import ErrorCode, MPIError

    slot = os.environ.get("OMPITPU_LOCAL_SLOT")
    where = (f"tpurun rank {int(os.environ['OMPITPU_NODE_ID']) - 1} "
             f"(local slot {slot} on host "
             f"{os.environ.get('OMPITPU_HOST', '?')})")
    try:
        platform = jax.local_devices()[0].platform
    except RuntimeError as e:
        raise MPIError(
            ErrorCode.ERR_OTHER,
            f"{where}: no usable device behind the slot — {e}",
        ) from e
    if (slot is not None and platform == "cpu"
            and not host_platform_declared()):
        raise MPIError(
            ErrorCode.ERR_OTHER,
            f"{where} has no TPU chip behind it: jax came up on the "
            "CPU. A rank never lands on the CPU by itself — map at "
            "most one rank per chip, or declare a host rank by "
            "starting it with JAX_PLATFORMS=cpu",
        )
    return str(platform)


class TpurunEss(mca_component.Component):
    """Bootstrap for processes launched by ``tpurun`` (the ess/env
    analogue: mpirun-launched procs detect the daemon's env vars,
    ``orte/mca/ess/env/ess_env_module.c:87``).

    Runs the FULL coordinator wire-up inside bring-up: JOIN + modex
    through the HNP, binomial tree link setup, the init barrier, and
    the heartbeat thread — so ``Runtime.init`` under tpurun flows
    through the OOB exactly like ``ompi_mpi_init.c:630-642,811`` flows
    through the daemon tree.
    """

    NAME = "tpurun"
    PRIORITY = 60  # above distributed: tpurun's env is more specific

    def register_vars(self) -> None:
        mca_var.register(
            "ess_tpurun_heartbeat_interval", "float", 0.5,
            "Seconds between worker heartbeats to the HNP "
            "(sensor_heartbeat.c:61 analogue)",
        )

    def query(self, ctx=None):
        if not os.environ.get("OMPITPU_HNP"):
            return None
        return (self.priority, self)

    def bootstrap(self):
        import jax

        from . import coordinator as coord

        platform = _bound_platform()  # before any wire-up: fail fast
        host, port = os.environ["OMPITPU_HNP"].rsplit(":", 1)
        node_id = int(os.environ["OMPITPU_NODE_ID"])
        num_workers = int(os.environ["OMPITPU_NUM_NODES"])
        import socket

        if (os.environ.get("OMPITPU_SECRET_STDIN") == "1"
                and not os.environ.get("OMPITPU_JOB_SECRET")):
            # rsh launches ship the job secret on stdin (a command-line
            # env assignment would be world-readable via /proc); it
            # must land before the first endpoint is created
            import sys as _sys

            os.environ["OMPITPU_JOB_SECRET"] = \
                read_stdin_secret(_sys.stdin)
        agent = coord.WorkerAgent(node_id, host, int(port))
        card = {
            "node_id": node_id,
            "pid": os.getpid(),
            # shm-reachability identity. OMPITPU_HOST_ID overrides the
            # UTS hostname: two containers can SHARE a hostname while
            # having separate /dev/shm (shm handoffs between them would
            # fail), and conversely test rigs use it to exercise the
            # DCN staging path on one machine — the btl_tcp_if_include
            # style of deployment knob
            "host": os.environ.get("OMPITPU_HOST_ID")
                    or socket.gethostname(),
            "local_device_count": jax.local_device_count(),
            "platform": platform,
        }
        # nativewire capability advertisement (ring token/geometry);
        # empty when the datapath is switched off. The .so itself is
        # already loaded — the OOB endpoint above cannot exist without
        # it — so nothing here can quietly withdraw the native path
        from ..btl import nativewire as _nativewire

        card.update(_nativewire.modex_entry())
        cards = agent.run_modex(card)  # launcher mode: workers only
        agent.setup_tree(num_workers + 1, cards)
        # FULL wire-up (superset of the tree edges): connect to every
        # lower-id peer so ANY worker pair holds a live OOB link — the
        # data plane the unified COMM_WORLD's cross-process transports
        # (runtime/wire.py) ride. The HIGHER id dials (same asymmetry
        # as tree links, where the child dials its parent); the lower
        # side's sends ride the accepted fd. The init barrier below
        # gates until every link is live.
        parent = coord.binomial_parent(node_id)
        from ..utils.errors import MPIError as _MPIError

        recovery = os.environ.get("OMPITPU_RECOVERY") == "1"
        for nid in range(1, node_id):
            if nid == parent:
                continue  # tree link already exists
            peer = cards[nid - 1]
            try:
                agent.ep.connect(nid, peer["oob_host"],
                                 int(peer["oob_port"]))
            except _MPIError:
                if not recovery:
                    # default policy: a dead peer address (typo'd
                    # hostfile, firewalled port) must fail the launch
                    # loudly, not surface later as a missing link
                    raise
                # resilient policy: the peer may have finished or be
                # mid-restart — the wire router raises a clear
                # ERR_UNREACH if this link is ever actually used
                _log.verbose(
                    1, f"wire-up: peer {nid} unreachable at "
                       f"{peer['oob_host']}:{peer['oob_port']} "
                       "(finished or restarting); continuing without "
                       "the link",
                )
        agent.barrier()  # every tree+wire edge live; init gate
        agent.start_heartbeats(
            float(mca_var.get("ess_tpurun_heartbeat_interval", 0.5))
        )
        _log.verbose(
            1, f"tpurun bootstrap: node {node_id}/{num_workers} wired"
        )
        return {
            "process_index": node_id - 1,
            "process_count": num_workers,
            "devices": jax.devices(),
            "local_devices": jax.local_devices(),
            "agent": agent,
            "peer_cards": cards,
        }


ESS_FRAMEWORK = mca_component.framework(
    "ess", "environment-specific bootstrap (orte/mca/ess analogue)"
)
ESS_FRAMEWORK.register(SingletonEss())
ESS_FRAMEWORK.register(DistributedEss())
ESS_FRAMEWORK.register(TpurunEss())
