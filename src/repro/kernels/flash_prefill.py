"""Pallas flash-attention kernel for prefill/training (TPU target,
interpret-validated on CPU).

Causal (optionally sliding-window, optionally softcapped) GQA attention
tiled for VMEM: (bq × D) query tiles stream against (bk × D) KV tiles with
the running (max, sumexp, accumulator) triple in VMEM scratch — the full
(S × S) score matrix never exists, matching models.common.chunked_attention
(the pure-jnp prefill path) tile for tile.

Grid: (B·H, Sq/bq, Skv/bk); the KV-head index is derived from the query
head (GQA sharing).  The last grid dim is sequential so the scratch triple
carries across KV tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            scale: float, causal: bool, window: int, attn_softcap: float,
            block_q: int, block_k: int, blocks_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32) * scale              # (bq, D)
    k = k_ref[0].astype(jnp.float32)                      # (bk, D)
    v = v_ref[0].astype(jnp.float32)                      # (bk, Dv)
    kv_len = len_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)

    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q)
    kv_pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)
    mask = kv_pos[None, :] < kv_len
    if causal:
        cm = kv_pos[None, :] <= q_pos[:, None]
        if window:
            cm &= kv_pos[None, :] > (q_pos[:, None] - window)
        mask = mask & cm
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None]) * (s > NEG_INF / 2)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1)
    acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_s[...] = m_safe

    @pl.when(ik == blocks_k - 1)
    def _fin():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...][:, None], 1e-30)
                    ).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  attn_softcap: float = 0.0, scale=None, kv_len=None,
                  block_q: int = 256, block_k: int = 256,
                  interpret: bool):
    """q: (B,S,H,D); k/v: (B,Skv,Hkv,Dv-compat); kv_len: optional (B,).
    Returns (B,S,H,Dv)."""
    B, S, H, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = H // Hkv
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)

    pq = (-S) % block_q
    pk = (-Skv) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    if kv_len is None:
        kv_len = jnp.full((B,), Skv, jnp.int32)
    Sp, Skp = S + pq, Skv + pk

    qf = jnp.swapaxes(q, 1, 2).reshape(B * H, Sp, D)
    kf = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, Skp, D)
    vf = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, Skp, Dv)

    grid = (B * H, Sp // block_q, Skp // block_k)

    def kv_idx(bh, iq, ik):
        return (bh // H * Hkv + (bh % H) // G, ik, 0)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        attn_softcap=attn_softcap, block_q=block_q, block_k=block_k,
        blocks_k=Skp // block_k)
    o = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bh, iq, ik: (bh // H,)),
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, Dv), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qf, kf, vf)
    o = jnp.swapaxes(o.reshape(B, H, Sp, Dv), 1, 2)
    return o[:, :S] if pq else o
