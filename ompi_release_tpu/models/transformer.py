"""TpuLM — the flagship decoder-only transformer, SPMD over the full
5-axis mesh (dp, pp, sp, ep, tp from ``parallel.mesh_axes``).

Every parallelism strategy of SURVEY §2.4 is load-bearing here:

  - batch sharded over (dp, ep); gradients of replicated params are
    psummed by shard_map's replication-tracking transpose (the ring
    allreduce of coll_tuned_allreduce.c:361, inserted by XLA)
  - trunk layers sharded over pp and pipelined with microbatch
    ppermute rings (``parallel.pp``)
  - sequence sharded over sp; attention is exact ring attention
    (``parallel.cp``) with RoPE carrying global positions
  - attention heads / FFN / vocab sharded over tp (``parallel.tp``)
  - optional switch-MoE FFN with experts sharded over ep
    (``parallel.ep``)

Pure-functional params (plain dict pytree), bf16 activations / f32
accumulation by default for the MXU.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import cp, ep as ep_mod, pp as pp_mod, tp as tp_mod
from ..parallel import tree as tree_mod
from ..utils import compile_cache


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    max_seq: int = 2048
    n_experts: int = 0  # 0 = dense FFN; >0 = switch-MoE every layer
    capacity_factor: float = 1.25
    microbatches: int = 1  # per-rank microbatch count for the pp schedule
    remat: bool = False  # jax.checkpoint the pipelined trunk (trade
    #                      recompute for activation memory)
    dtype: Any = jnp.bfloat16
    rope_base: float = 10000.0
    # attention implementation: "auto" = Pallas flash kernel when the
    # mesh is TPU devices and the sequence is unsharded, ring attention
    # otherwise (resolved from the mesh by the jitted entry points);
    # "ring" / "flash" force one path (flash runs interpreted off-TPU)
    attn_impl: str = "auto"

    def validate(self, mesh: Mesh) -> None:
        ax = dict(mesh.shape)
        if self.n_layers % ax.get("pp", 1):
            raise ValueError("n_layers must divide by pp")
        if self.n_heads % ax.get("tp", 1):
            raise ValueError("n_heads must divide by tp")
        if self.vocab % ax.get("tp", 1):
            raise ValueError("vocab must divide by tp")
        if self.d_ff % ax.get("tp", 1):
            raise ValueError("d_ff must divide by tp")
        if self.n_experts and self.n_experts % ax.get("ep", 1):
            raise ValueError("n_experts must divide by ep")


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict:
    """Global (unsharded) parameter pytree; shard with param_specs."""
    k = jax.random.split(rng, 10)
    d, l = cfg.d_model, cfg.n_layers
    hdim = cfg.n_heads * cfg.head_dim
    dt = cfg.dtype

    def norm(key, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    params = {
        "embed": norm(k[0], cfg.vocab, d, scale=0.02),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((l, d), jnp.float32),
            "wq": norm(k[1], l, d, hdim),
            "wk": norm(k[2], l, d, hdim),
            "wv": norm(k[3], l, d, hdim),
            "wo": norm(k[4], l, hdim, d),
            "ln2": jnp.ones((l, d), jnp.float32),
        },
    }
    if cfg.n_experts:
        params["layers"]["router"] = norm(
            k[5], l, d, cfg.n_experts, scale=0.02
        ).astype(jnp.float32)
        params["layers"]["we1"] = norm(k[6], l, cfg.n_experts, d, cfg.d_ff)
        params["layers"]["we2"] = norm(k[7], l, cfg.n_experts, cfg.d_ff, d)
    else:
        params["layers"]["w1"] = norm(k[6], l, d, cfg.d_ff)
        params["layers"]["w2"] = norm(k[7], l, cfg.d_ff, d)
    return params


#: regex partition rules, first match wins — the user-facing sharding
#: interface (``parallel.tree.match_partition_rules``): which mesh
#: axis owns which tensor dimension, keyed by parameter path name.
#: Scalar/single-element leaves are never partitioned (the planner's
#: fmengine rule), so the table only needs the real tensors.
PARTITION_RULES = (
    (r"^embed$", P("tp", None)),
    (r"^ln_f$", P()),
    (r"layers/ln[12]$", P("pp", None)),
    (r"layers/w[qkv]$", P("pp", None, "tp")),
    (r"layers/wo$", P("pp", "tp", None)),
    (r"layers/router$", P("pp", None, None)),
    (r"layers/we[12]$", P("pp", "ep", None, None)),
    (r"layers/w1$", P("pp", None, "tp")),
    (r"layers/w2$", P("pp", "tp", None)),
)


def param_specs(cfg: ModelConfig) -> Dict:
    """PartitionSpecs matching init_params' structure, derived by
    matching :data:`PARTITION_RULES` against an abstract parameter
    skeleton (``jax.eval_shape`` — no arrays materialize). An
    unmatched leaf raises at build time, so adding a parameter without
    a rule cannot silently default to replicated."""
    skeleton = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    return tree_mod.match_partition_rules(PARTITION_RULES, skeleton)


def batch_spec() -> P:
    return P(("dp", "ep"), "sp")


# ---------------------------------------------------------------------------
# layers (per-rank SPMD code)
# ---------------------------------------------------------------------------

def _rmsnorm(x: jax.Array, g: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * r * g).astype(x.dtype)


def _rope(x: jax.Array, pos: jax.Array, base: float) -> jax.Array:
    """x: (mb, S, H, Dh); pos: (S,) global positions."""
    dh = x.shape[-1]
    half = dh // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None]  # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32
    )
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _layer(cfg: ModelConfig, lp: Dict, x: jax.Array) -> jax.Array:
    """One transformer block. x: (mb, S_loc, D) per rank."""
    sp_n = lax.psum(1, "sp")
    sp_idx = lax.axis_index("sp")
    s_loc = x.shape[1]
    pos = sp_idx * s_loc + jnp.arange(s_loc)

    h = _rmsnorm(x, lp["ln1"])
    mb = x.shape[0]
    hl = lp["wq"].shape[-1] // cfg.head_dim  # local heads (H/tp)

    def qkv(w):
        y = tp_mod.column_parallel(h, w, axis_name="tp")
        return y.reshape(mb, s_loc, hl, cfg.head_dim)

    q = _rope(qkv(lp["wq"]), pos, cfg.rope_base)
    k = _rope(qkv(lp["wk"]), pos, cfg.rope_base)
    v = qkv(lp["wv"])

    # attention: Pallas flash kernel when the sequence is local to one
    # device; exact ring attention over the sp axis otherwise
    if cfg.attn_impl == "flash" and sp_n > 1:
        raise ValueError(
            "attn_impl='flash' is single-shard attention; with sp>1 "
            "use 'ring' (or 'auto', which picks ring for sharded seq)"
        )
    if cfg.attn_impl == "flash":
        from ..ops.pallas_attention import flash_attention

        attn_fn = lambda q1, k1, v1: flash_attention(q1, k1, v1, True)
    else:
        attn_fn = lambda q1, k1, v1: cp.ring_attention(
            q1, k1, v1, axis_name="sp", causal=True
        )
    attn = jax.vmap(attn_fn)(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3))
    attn = attn.transpose(0, 2, 1, 3).reshape(mb, s_loc, hl * cfg.head_dim)
    x = x + tp_mod.row_parallel(attn, lp["wo"], axis_name="tp")

    h2 = _rmsnorm(x, lp["ln2"])
    if cfg.n_experts:
        tokens = h2.reshape(mb * s_loc, cfg.d_model)

        def expert_fn(pe, t):
            w1, w2 = pe
            u = jnp.matmul(t, w1, preferred_element_type=jnp.float32)
            u = jax.nn.gelu(u).astype(t.dtype)
            return jnp.matmul(u, w2,
                              preferred_element_type=jnp.float32).astype(
                t.dtype
            )

        out, _aux = ep_mod.moe_layer(
            tokens, lp["router"], expert_fn, (lp["we1"], lp["we2"]),
            axis_name="ep", capacity_factor=cfg.capacity_factor,
        )
        x = x + out.reshape(mb, s_loc, cfg.d_model)
    else:
        u = tp_mod.column_parallel(h2, lp["w1"], axis_name="tp")
        u = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
        x = x + tp_mod.row_parallel(u, lp["w2"], axis_name="tp")
    return x


def _trunk(cfg: ModelConfig, stage_layers: Dict, x: jax.Array) -> jax.Array:
    """This pp rank's layers, scanned. x: (mb, S_loc, D)."""
    def body(x, lp):
        return _layer(cfg, lp, x), None

    x, _ = lax.scan(body, x, stage_layers)
    return x


# ---------------------------------------------------------------------------
# full forward / loss (runs under shard_map over the 5-axis mesh)
# ---------------------------------------------------------------------------

def forward_loss(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                 targets: jax.Array) -> jax.Array:
    """Replicated scalar mean-xent loss. tokens/targets: (b_loc, S_loc)."""
    pp_n = lax.psum(1, "pp")
    pp_idx = lax.axis_index("pp")
    b_loc, s_loc = tokens.shape
    m = cfg.microbatches
    mb = b_loc // m

    emb = tp_mod.vocab_parallel_embedding(
        tokens, params["embed"], axis_name="tp"
    ).astype(cfg.dtype)
    x_mb = emb.reshape(m, mb, s_loc, cfg.d_model)

    y = pp_mod.pipeline(
        partial(_trunk, cfg), params["layers"], x_mb, axis_name="pp",
        remat=cfg.remat,
    )  # (m, mb, S_loc, D), meaningful on the last stage

    h = _rmsnorm(y.reshape(b_loc, s_loc, cfg.d_model), params["ln_f"])
    nll = tp_mod.vocab_parallel_xent(
        h.astype(jnp.float32), params["embed"].astype(jnp.float32),
        targets, axis_name="tp",
    )  # (b_loc, S_loc)

    # global mean over all tokens: local sum / static global count
    dp_n, ep_n, sp_n = (lax.psum(1, a) for a in ("dp", "ep", "sp"))
    total = b_loc * s_loc * dp_n * ep_n * sp_n
    local = jnp.sum(nll) / total
    # only the last pp stage's value is real; psum over every axis both
    # broadcasts it and (through shard_map's replication-tracked
    # transpose) routes gradient flow correctly
    masked = jnp.where(pp_idx == pp_n - 1, local, jnp.zeros_like(local))
    return lax.psum(masked, ("dp", "pp", "sp", "ep"))


# ---------------------------------------------------------------------------
# jitted entry points
# ---------------------------------------------------------------------------

def _loss_spmd(cfg: ModelConfig, mesh: Mesh):
    # interpret-mode pallas (flash off-TPU, the CI simulator) trips
    # jax's vma checker inside the HLO interpreter (dynamic_slice
    # "varying manual axes must match", jax-ml/jax — the checker, not
    # the math: the compiled TPU path type-checks and the kernel is
    # verified against the dense reference both directions in
    # tests/test_pallas.py). Disable the check exactly there, keeping
    # it live for every other configuration.
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    if cfg.attn_impl == "auto":
        # decided by where the operands live (the mesh), not by the
        # process's default backend
        flash = on_tpu and mesh.shape["sp"] == 1
        cfg = dataclasses.replace(
            cfg, attn_impl="flash" if flash else "ring")
    check_vma = not (cfg.attn_impl == "flash" and not on_tpu)
    return jax.shard_map(
        partial(forward_loss, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg), batch_spec(), batch_spec()),
        out_specs=P(),
        check_vma=check_vma,
    )


def make_forward(cfg: ModelConfig, mesh: Mesh):
    """Jitted loss-evaluation forward step (the flagship inference/eval
    path); returns fn(params, tokens, targets) -> scalar loss."""
    cfg.validate(mesh)
    compile_cache.ensure()
    return jax.jit(_loss_spmd(cfg, mesh))


def make_train_step(cfg: ModelConfig, mesh: Mesh, optimizer):
    """Jitted full train step over the mesh.

    The grad is taken through the shard_map'd loss; optimizer update
    runs under the same jit with shardings propagated from the params,
    so the whole step is ONE compiled program (no per-step retrace, the
    north-star requirement of SURVEY §6).
    """
    cfg.validate(mesh)
    compile_cache.ensure()
    loss_fn = _loss_spmd(cfg, mesh)

    @jax.jit
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
        return params, opt_state, loss

    return step


def shard_params(params: Dict, cfg: ModelConfig, mesh: Mesh) -> Dict:
    """Device_put the global params onto the mesh per param_specs."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(cfg),
    )


def make_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())
