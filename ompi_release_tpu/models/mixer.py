"""TpuMixer — a second model family over the same parallel substrate.

MLP-Mixer (token-mixing MLP across patches + channel-mixing MLP across
features) is the all-matmul counterpoint to the attention-based
flagship: no softmax, no sequence ring — pure MXU work, which is
exactly the shape the substrate's TP/DP components were built for:

  - channel-mixing MLPs shard over ``tp`` with the same
    column-parallel/row-parallel pair the transformer's FFN uses
    (``parallel.tp`` — one psum per block, coll_tuned_allreduce's
    role inserted by shard_map's transpose);
  - the batch shards over ``dp``; replicated-parameter gradients are
    psummed by the same replication-tracking transpose as the
    flagship;
  - token mixing operates on the (small) patch axis and stays
    replicated across tp — sharding it would trade one transpose for
    an all-to-all with no arithmetic win at Mixer's patch counts.

Same functional conventions as ``models.transformer``: plain-dict
params, ``param_specs`` PartitionSpecs, ``make_forward`` /
``make_train_step`` jitted entry points over a mesh from
``parallel.mesh_axes.build_parallel_mesh``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import tp as tp_mod
from ..utils import compile_cache


@dataclasses.dataclass(frozen=True)
class MixerConfig:
    n_patches: int = 64
    d_model: int = 128
    d_token: int = 64     # token-mixing hidden dim
    d_channel: int = 512  # channel-mixing hidden dim (tp-sharded)
    n_layers: int = 4
    n_classes: int = 10
    dtype: Any = jnp.bfloat16

    def validate(self, mesh: Mesh) -> None:
        ax = dict(mesh.shape)
        if self.d_channel % ax.get("tp", 1):
            raise ValueError("d_channel must divide by tp")
        for name in ("pp", "sp", "ep"):
            if ax.get(name, 1) != 1:
                raise ValueError(
                    f"TpuMixer parallelizes over dp/tp only; axis "
                    f"'{name}' must be 1 (got {ax[name]})"
                )


def init_params(rng: jax.Array, cfg: MixerConfig) -> Dict:
    k = jax.random.split(rng, 5)
    dt = cfg.dtype

    def norm(key, *shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dt)

    l = cfg.n_layers
    return {
        "layers": {
            "ln1": jnp.ones((l, cfg.d_model), jnp.float32),
            # token mixing: operates on the patch axis (replicated)
            "wt1": norm(k[0], l, cfg.n_patches, cfg.d_token),
            "wt2": norm(k[1], l, cfg.d_token, cfg.n_patches),
            "ln2": jnp.ones((l, cfg.d_model), jnp.float32),
            # channel mixing: the FFN pair, tp-sharded
            "wc1": norm(k[2], l, cfg.d_model, cfg.d_channel),
            "wc2": norm(k[3], l, cfg.d_channel, cfg.d_model),
        },
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "head": norm(k[4], cfg.d_model, cfg.n_classes),
    }


def param_specs(cfg: MixerConfig) -> Dict:
    return {
        "layers": {
            "ln1": P(None, None),
            "wt1": P(None, None, None),
            "wt2": P(None, None, None),
            "ln2": P(None, None),
            "wc1": P(None, None, "tp"),   # column parallel
            "wc2": P(None, "tp", None),   # row parallel
        },
        "ln_f": P(),
        "head": P(None, None),
    }


def batch_spec() -> P:
    return P("dp")


def _layernorm(x: jax.Array, g: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-6) * g).astype(x.dtype)


def _layer(lp: Dict, x: jax.Array) -> jax.Array:
    """One mixer block. x: (B_loc, P, D)."""
    # token mixing across patches (replicated weights)
    h = _layernorm(x, lp["ln1"])
    h = jnp.swapaxes(h, 1, 2)  # (B, D, P)
    h = jnp.einsum("bdp,pt->bdt", h, lp["wt1"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.gelu(h)
    h = jnp.einsum("bdt,tp->bdp", h, lp["wt2"],
                   preferred_element_type=jnp.float32)
    x = x + jnp.swapaxes(h, 1, 2).astype(x.dtype)

    # channel mixing: the tp-sharded FFN pair (one psum, in row_parallel)
    h = _layernorm(x, lp["ln2"])
    h = tp_mod.column_parallel(h, lp["wc1"], axis_name="tp")
    h = jax.nn.gelu(h)
    h = tp_mod.row_parallel(h, lp["wc2"], axis_name="tp")
    return x + h.astype(x.dtype)


def forward_loss(cfg: MixerConfig, params: Dict, patches: jax.Array,
                 labels: jax.Array) -> jax.Array:
    """patches: (B_loc, P, D) pre-embedded patch features;
    labels: (B_loc,) int32. Returns the global mean xent."""
    x = patches.astype(cfg.dtype)

    def body(x, lp):
        return _layer(lp, x), None

    x, _ = lax.scan(body, x, params["layers"])
    x = _layernorm(x, params["ln_f"])
    pooled = jnp.mean(x.astype(jnp.float32), axis=1)  # (B, D)
    logits = pooled @ params["head"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    dp_n = lax.psum(1, "dp")
    total = nll.shape[0] * dp_n
    return lax.psum(jnp.sum(nll) / total, "dp")


def _loss_spmd(cfg: MixerConfig, mesh: Mesh):
    return jax.shard_map(
        partial(forward_loss, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg), batch_spec(), batch_spec()),
        out_specs=P(),
    )


def shard_params(params: Dict, cfg: MixerConfig, mesh: Mesh) -> Dict:
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(cfg),
    )


def make_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def make_forward(cfg: MixerConfig, mesh: Mesh):
    cfg.validate(mesh)
    compile_cache.ensure()
    return jax.jit(_loss_spmd(cfg, mesh))


def make_train_step(cfg: MixerConfig, mesh: Mesh, optimizer):
    cfg.validate(mesh)
    compile_cache.ensure()
    loss_fn = _loss_spmd(cfg, mesh)

    @jax.jit
    def step(params, opt_state, patches, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, patches, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
        return params, opt_state, loss

    return step
