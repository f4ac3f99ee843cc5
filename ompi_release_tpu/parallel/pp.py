"""Pipeline parallelism: GPipe-style microbatch schedule over a ppermute
ring.

The stage-to-stage activation transfer is exactly the reference's
point-to-point ring (``examples/ring_c.c:39-61``) compiled into one XLA
program: each tick every stage computes its block and ppermutes the
activation to stage+1. Runs under ``shard_map`` over the ``pp`` axis;
each rank holds only its own stage's parameters (stacked stage params
are sharded over pp by the caller's PartitionSpec).

Schedule: M microbatches through S stages in M+S-1 ticks via
``lax.scan`` — static shapes, no data-dependent control flow; the
bubble is (S-1)/(M+S-1), so callers pick M >= 4*S.
"""

from __future__ import annotations

import time as _time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..mca import pvar

_boundary_msgs = pvar.counter(
    "pp_boundary_msgs", "host-pipeline stage-boundary activations sent"
)
_boundary_wait = pvar.timer(
    "pp_boundary_wait_seconds",
    "EXPOSED host-pipeline boundary-transfer time (recv wait the "
    "stage could not hide in its microbatch compute)",
)


def pipeline(stage_fn: Callable, stage_params, x_microbatches: jax.Array, *,
             axis_name: str = "pp", remat: bool = False) -> jax.Array:
    """Run microbatches through the stage pipeline.

    stage_fn(params, x) -> y with y.shape == x.shape (transformer blocks
    satisfy this; stage 0/S-1 asymmetries like embed/unembed belong
    outside the pipelined trunk).

    x_microbatches: (M, ...) — the microbatched input, meaningful on
    stage 0 (other stages may pass anything of the same shape, e.g. the
    same array; only stage 0's values are consumed).
    Returns (M, ...) — meaningful on the last stage.

    ``remat=True`` wraps the stage body in ``jax.checkpoint``: the
    backward pass recomputes each tick's activations instead of
    keeping all M x S of them live — the TPU-idiomatic answer to the
    activation-memory problem 1F1B schedules solve by hand elsewhere
    (the schedule stays the compiled scan; XLA plans the recompute).
    Gradients are bitwise-equivalent math, just cheaper to hold.
    """
    if remat:
        # prevent_cse=False is the documented form for checkpoint
        # under scan: the CSE hazard the default guards against cannot
        # occur here, and its barriers would block XLA fusion across
        # the remat boundary
        stage_fn = jax.checkpoint(stage_fn, prevent_cse=False)
    n = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    ticks = m + n - 1
    fwd = [(i, i + 1) for i in range(n - 1)]

    from .mesh_axes import vary_like, vary_over

    # carries end up varying over pp (stage-dependent) on top of the
    # input's own varying axes; type the initial values to match
    ref = vary_over(x_microbatches, (axis_name,))
    outputs = vary_like(jnp.zeros_like(x_microbatches), ref)
    recv0 = vary_like(jnp.zeros_like(x_microbatches[0]), ref)
    x_microbatches = ref

    def tick(carry, t):
        recv, outputs = carry
        mb = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, m - 1), 0, keepdims=False
        )
        inp = jnp.where(stage == 0, mb, recv)
        out = stage_fn(stage_params, inp)
        # last stage stores microbatch t-(n-1) once it exists
        oidx = jnp.clip(t - (n - 1), 0, m - 1)
        cur = lax.dynamic_index_in_dim(outputs, oidx, 0, keepdims=False)
        store = jnp.where((t >= n - 1) & (stage == n - 1), out, cur)
        outputs = lax.dynamic_update_index_in_dim(outputs, store, oidx, 0)
        recv = lax.ppermute(out, axis_name, fwd) if n > 1 else recv
        return (recv, outputs), None

    (_, outputs), _ = lax.scan(tick, (recv0, outputs), jnp.arange(ticks))
    return outputs


def pipeline_loss(stage_fn: Callable, loss_fn: Callable, stage_params,
                  x_microbatches: jax.Array, target_microbatches, *,
                  axis_name: str = "pp", remat: bool = False) -> jax.Array:
    """Forward pipeline + last-stage loss, broadcast to all stages.

    ``loss_fn(y, targets) -> scalar`` runs on the last stage's outputs;
    the psum-of-masked-value broadcast gives every stage the same scalar
    so ``jax.grad`` through this function produces each stage's local
    parameter gradients (XLA transposes the ppermutes into the backward
    ring automatically — the reference's reverse activation ring).
    """
    n = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    y = pipeline(stage_fn, stage_params, x_microbatches,
                 axis_name=axis_name, remat=remat)
    local = loss_fn(y, target_microbatches)
    # Only the last stage's loss is real. The value is broadcast with a
    # psum of the masked term, but the psum must be OUTSIDE the grad
    # path: psum's transpose is psum, so differentiating the broadcast
    # on every rank would scale gradients by n. stop_gradient routes
    # backward flow solely through the last stage's local term (whose
    # cotangent then rides the transposed ppermute ring to every stage).
    masked = jnp.where(stage == n - 1, local, jnp.zeros_like(local))
    bcast = lax.psum(masked, axis_name)
    return masked + lax.stop_gradient(bcast - masked)


# ---------------------------------------------------------------------------
# host-driver microbatch schedule (spanning comms; nonblocking boundaries)
# ---------------------------------------------------------------------------

class HostPipeline:
    """GPipe microbatch schedule driven from the host over a
    communicator: each member rank is one stage, boundary activations
    ride rank-to-rank messages instead of a compiled ppermute ring
    (the multi-process trainer shape, where stages live in different
    controller processes).

    With ``nonblocking=True`` (default) every boundary transfer is an
    ``irecv`` posted UP FRONT and an ``isend`` never waited mid-
    schedule — the PR 7 progress engine moves the bytes while the
    stage computes its next microbatch, so the pipeline bubble hides
    the communication (exposed remainder witnessed by the
    ``pp_boundary_wait_seconds`` pvar; with the ``progress_thread``
    cvar on, spanning transfers complete off the caller entirely).
    ``nonblocking=False`` is the blocking reference leg: every
    boundary send+recv runs exposed between two computes.

    The schedule is the same M+S-1-tick GPipe wavefront as
    :func:`pipeline`; results are bitwise-identical between the two
    legs (same stage_fn calls in the same order, comm is pure data
    movement).
    """

    def __init__(self, comm, stage_fn: Callable, *,
                 stage: Optional[int] = None, tag: int = 71,
                 nonblocking: bool = True) -> None:
        self.comm = comm
        self.stage_fn = stage_fn
        if stage is None:
            ranks = getattr(comm, "local_comm_ranks", None)
            stage = ranks[0] if ranks else 0
        self.stage = int(stage)
        self.tag = tag
        self.nonblocking = nonblocking

    def run(self, microbatches: Sequence[Any]) -> List[Any]:
        """Stream ``microbatches`` through this process's stage.
        Stage 0 consumes the inputs; the last stage returns the list
        of outputs (other stages return [])."""
        comm, s, tag = self.comm, self.stage, self.tag
        n_stages = comm.size
        m = len(microbatches)
        nb = self.nonblocking
        recvs: List[Any] = []
        if s > 0 and nb:
            # every boundary irecv posts before the first compute:
            # upstream activations land during our earlier-microbatch
            # computes (the bubble), not in an exposed wait
            recvs = [comm.irecv(s - 1, tag, rank=s) for _ in range(m)]
        outs: List[Any] = []
        sends: List[Any] = []
        for k in range(m):
            if s == 0:
                x = microbatches[k]
            else:
                t0 = _time.perf_counter()
                if nb:
                    req = recvs[k]
                    req.wait()
                    x = req.value
                else:
                    x, _st = comm.recv(s - 1, tag, rank=s)
                _boundary_wait.add(_time.perf_counter() - t0)
            y = self.stage_fn(x)
            if s < n_stages - 1:
                _boundary_msgs.add()
                if nb:
                    # fire and keep computing; drained at schedule end
                    sends.append(comm.isend(y, s + 1, tag, rank=s))
                else:
                    comm.send(y, s + 1, tag, rank=s)
            else:
                outs.append(y)
        for req in sends:
            req.wait()
        return outs
