"""Pallas flash-attention kernel — the hand-scheduled hot op.

The one place XLA's automatic fusion loses to hand scheduling in this
framework's model stack is attention: materializing (S, S) scores is
HBM-bound, while a blocked kernel keeps the working set in VMEM and
streams K/V blocks through the MXU with an online softmax. This is the
``op`` framework's accelerated-component story (SURVEY §2.3: "op MCA
framework exists for accelerated overrides") applied where it matters.

Layout: q/k/v are (H, S, D). Grid = (H, S/block_q); each program owns
one query block, loops over key blocks with running (max, sumexp)
statistics in f32 and emits the per-row logsumexp (LSE) alongside the
output. Backward is fully blocked too (the flash recompute strategy):
two Pallas kernels — dq over q-blocks, dk/dv over k-blocks — re-derive
each probability block from q/k and the saved LSE, so no (S, S)
tensor is ever materialized in either direction.

``interpret=True`` runs the same kernels on CPU for CI (the simulator
backend strategy of SURVEY §4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                 seq_k: int, causal: bool, block_q: int):
    """One (head, q-block) program: stream K/V blocks, online softmax.
    Also emits the per-row logsumexp of the scaled scores — the (m, l)
    statistic the blocked backward recomputes probabilities from."""
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    d = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(d))
    q = q * scale

    nk = pl.cdiv(seq_k, block_k)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(jk, carry):
        acc, row_m, row_l = carry
        k_blk = k_ref[0, pl.ds(jk * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[0, pl.ds(jk * block_k, block_k), :].astype(
            jnp.float32
        )
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_k  # tail padding
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.maximum(row_m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m[:, None])
        alpha = jnp.exp(row_m - m)
        acc = acc * alpha[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        row_l = row_l * alpha + jnp.sum(p, axis=-1)
        return acc, m, row_l

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, row_m, row_l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    out = acc / jnp.maximum(row_l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # LSE stays finite for fully-masked (padding) rows: m is the
    # finite NEG_INF sentinel and l is clamped, so the backward's
    # exp(s - lse) cannot produce inf*0 NaNs on masked entries
    lse_ref[0, :, 0] = row_m + jnp.log(jnp.maximum(row_l, 1e-30))


def _flash_forward(q, k, v, *, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    h, s, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(s, bk)
    # pad both sequence axes to whole blocks: a dynamic slice whose
    # start exceeds the buffer gets CLAMPED, which would silently read
    # the wrong K/V rows on the final partial block
    pad_q = nq * bq - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    pad_k = nk * bk - s
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    sk = s + pad_k

    kernel = functools.partial(
        _attn_kernel, block_k=bk, seq_k=s, causal=causal, block_q=bq,
    )
    vma = getattr(jax.typeof(q), "vma", frozenset())
    out, lse = pl.pallas_call(
        kernel,
        grid=(h, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda ih, iq: (ih, iq, 0)),
            pl.BlockSpec((1, sk, d), lambda ih, iq: (ih, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda ih, iq: (ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda ih, iq: (ih, iq, 0)),
            # LSE rides as (H, S, 1): Mosaic requires the last two
            # block dims be (8k, 128k)-divisible or full, which a
            # (1, bq) block of an (H, S) array cannot satisfy
            pl.BlockSpec((1, bq, 1), lambda ih, iq: (ih, iq, 0)),
        ],
        # under shard_map's replication tracking the kernel output
        # varies over the same manual axes as its inputs
        out_shape=[
            jax.ShapeDtypeStruct((h, nq * bq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((h, nq * bq, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s, :], lse[:, :s, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
                   *, block_k: int, seq_q: int, seq_k: int, causal: bool,
                   block_q: int):
    """dq for one (head, q-block): stream K/V blocks, recompute each
    probability block P = exp(S - LSE) from the saved statistic —
    never an (S, S) tensor, exactly the forward's blocking."""
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    dlt = dlt_ref[0, :, 0]
    d = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(d))
    qs = q * scale

    nk = pl.cdiv(seq_k, block_k)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(jk, dq):
        k_blk = k_ref[0, pl.ds(jk * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[0, pl.ds(jk * block_k, block_k), :].astype(
            jnp.float32
        )
        s = jnp.dot(qs, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = (q_pos < seq_q) & (k_pos < seq_k)
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dlt[:, None])
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, nk, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    dk_ref, dv_ref, *, block_q: int, seq_q: int,
                    seq_k: int, causal: bool, block_k: int):
    """dk/dv for one (head, k-block): stream q/dO/LSE blocks."""
    jk = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    d = k_blk.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(d))

    nq = pl.cdiv(seq_q, block_q)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    def body(iq, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(iq * block_q, block_q), :].astype(
            jnp.float32
        )
        do_blk = do_ref[0, pl.ds(iq * block_q, block_q), :].astype(
            jnp.float32
        )
        lse_blk = lse_ref[0, pl.ds(iq * block_q, block_q), 0]
        dlt_blk = dlt_ref[0, pl.ds(iq * block_q, block_q), 0]
        qs = q_blk * scale
        s = jnp.dot(qs, k_blk.T, preferred_element_type=jnp.float32)
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        mask = (q_pos < seq_q) & (k_pos < seq_k)
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
        dv = dv + jnp.dot(p.T, do_blk,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v_blk.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - dlt_blk[:, None])
        # dk_j = sum_i ds_ij * scale * q_i  (qs already carries scale)
        dk = dk + jnp.dot(ds.T, qs, preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal: bool, block_q: int,
                    block_k: int, interpret: bool):
    """Blocked flash backward: delta = rowsum(dO*O) host-side (O(S·D)
    elementwise), then one Pallas sweep per gradient side."""
    h, s, d = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(s, bk)
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (h, s)

    def pad_seq(x, n_blocks, blk):
        padding = n_blocks * blk - s
        if padding == 0:
            return x
        cfg = ((0, 0), (0, padding)) + ((0, 0),) * (x.ndim - 2)
        return jnp.pad(x, cfg)

    qp = pad_seq(q, nq, bq)
    dop = pad_seq(g, nq, bq)
    lsep = pad_seq(lse, nq, bq)[..., None]   # (h, sq, 1): see forward
    dltp = pad_seq(delta, nq, bq)[..., None]
    kp = pad_seq(k, nk, bk)
    vp = pad_seq(v, nk, bk)
    sq, sk = nq * bq, nk * bk
    vma = getattr(jax.typeof(q), "vma", frozenset())

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=bk, seq_q=s, seq_k=s,
            causal=causal, block_q=bq,
        ),
        grid=(h, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda ih, iq: (ih, iq, 0)),
            pl.BlockSpec((1, sk, d), lambda ih, iq: (ih, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda ih, iq: (ih, 0, 0)),
            pl.BlockSpec((1, bq, d), lambda ih, iq: (ih, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda ih, iq: (ih, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda ih, iq: (ih, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda ih, iq: (ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype, vma=vma),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dltp)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=bq, seq_q=s, seq_k=s,
            causal=causal, block_k=bk,
        ),
        grid=(h, nk),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda ih, jk: (ih, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda ih, jk: (ih, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda ih, jk: (ih, jk, 0)),
            pl.BlockSpec((1, sq, d), lambda ih, jk: (ih, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda ih, jk: (ih, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda ih, jk: (ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda ih, jk: (ih, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda ih, jk: (ih, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, sk, d), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((h, sk, d), v.dtype, vma=vma),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dltp)
    return dq[:, :s, :], dk[:, :s, :], dv[:, :s, :]


def _reference(q, k, v, causal: bool):
    d = q.shape[-1]
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * jax.lax.rsqrt(jnp.float32(d))
    if causal:
        n = q.shape[1]
        i = jnp.arange(n)
        s = jnp.where(i[:, None] >= i[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Blocked attention. q/k/v: (H, S, D); returns (H, S, D).

    ``interpret=None`` auto-selects: compiled on TPU, interpreter
    elsewhere (CI parity runs).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _ = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    # residuals: inputs + output + per-row LSE — O(S·D), never (S, S)
    return out, (q, k, v, out, lse, interpret)


def _bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, interp = res
    return _flash_backward(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interp,
    )


flash_attention.defvjp(_fwd, _bwd)
