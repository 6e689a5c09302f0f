"""Pallas flash-decode GQA kernel (TPU target, interpret-validated on CPU).

The TPU-native replacement for the paper's MKL CPU-GQA kernel: one decode
step of grouped-query attention against a (possibly ring-buffered,
sequence-sharded) KV cache.  The KV sequence is tiled into VMEM blocks;
a running (max, sumexp, accumulator) triple lives in VMEM scratch across
the sequential KV-block grid dimension, so HBM traffic is exactly one read
of K and V — the kernel is memory-roof-bound by construction, which is
what the HRM analysis (Fig. 4) says decode attention must be.

Returns *partials* (o_unnorm, m, l) so the sequence-sharded combine
(distributed.collectives.lse_combine) can merge shards — the kernel slots
directly under the paper's "compute attention where the KV lives" rule.

Layout notes:
  * q is pre-reshaped to (B, Hkv, G, D): the G*D tile is MXU-aligned for
    G=8..128 query groups.
  * K/V blocks are (block_w, D) tiles per (batch, kv-head) — contiguous in
    the cache layout (B, W, Hkv, D) after a transpose the wrapper does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, valid_ref, *rest,
            scale: float, attn_softcap: float, blocks_w: int,
            quantized: bool):
    if quantized:       # int8 arena: per-(token, head) dequant scales
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = rest
    else:
        o_ref, m_ref, l_ref, acc, m_s, l_s = rest
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (bw, D)
    v = v_ref[0, 0].astype(jnp.float32)                # (bw, Dv)
    valid = valid_ref[0] != 0                          # (1, bw)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (G, bw)
    if quantized:       # fold k_scale per tile: s = (q . k_int) * ks
        s = s * ks_ref[0, 0]
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]                                  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe) * (s > NEG_INF / 2)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                     jnp.exp(m_prev - m_safe))
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    if quantized:       # fold v_scale into p: o = (p * vs) @ v_int
        p = p * vs_ref[0, 0]
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))                # (G, Dv)
    # keep the TRUE running max (NEG_INF while nothing valid yet) so the
    # emitted m matches the single-pass oracle even when an all-invalid
    # block precedes a block whose true max is negative
    m_s[...] = m_new

    @pl.when(w == blocks_w - 1)
    def _fin():
        o_ref[0, 0] = acc[...]
        m_ref[0, 0] = jnp.where(m_s[...] <= NEG_INF / 2, 0.0, m_s[...])
        l_ref[0, 0] = l_s[...]


def gqa_decode(q, k, v, valid, *, scale: float, attn_softcap: float = 0.0,
               k_scale=None, v_scale=None, block_w: int = 512,
               interpret: bool):
    """q: (B,H,D); k: (B,W,Hkv,D); v: (B,W,Hkv,Dv); valid: (B,W) bool.
    int8 caches pass k_scale/v_scale (B,W,Hkv) f32 — the dequant runs
    tile-wise in VMEM, never as a materialized f32 ring.
    Returns (o_unnorm (B,H,Dv) f32, m (B,H) f32, l (B,H) f32).

    TPU tiling: the validity mask (as int32) and the scale rows ride with
    a unit axis before their minor dim, so every block's last two dims
    span the array's or are (8, 128)-divisible."""
    B, H, D = q.shape
    _, W, Hkv, Dv = v.shape
    G = H // Hkv
    block_w = min(block_w, W)
    assert W % block_w == 0, (W, block_w)
    blocks_w = W // block_w
    quantized = k_scale is not None

    qg = q.reshape(B, Hkv, G, D)
    kt = jnp.swapaxes(k, 1, 2)           # (B, Hkv, W, D)
    vt = jnp.swapaxes(v, 1, 2)           # (B, Hkv, W, Dv)

    grid = (B, Hkv, blocks_w)
    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, w: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, block_w, D), lambda b, h, w: (b, h, w, 0)),
        pl.BlockSpec((1, 1, block_w, Dv), lambda b, h, w: (b, h, w, 0)),
        pl.BlockSpec((1, 1, block_w), lambda b, h, w: (b, 0, w)),
    ]
    inputs = [qg, kt, vt, valid.astype(jnp.int32).reshape(B, 1, W)]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, block_w),
                                  lambda b, h, w: (b, h, 0, w))] * 2
        inputs += [jnp.swapaxes(k_scale, 1, 2).reshape(B, Hkv, 1, W),
                   jnp.swapaxes(v_scale, 1, 2).reshape(B, Hkv, 1, W)]
    out_shapes = (
        jax.ShapeDtypeStruct((B, Hkv, G, Dv), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
    )
    kern = functools.partial(_kernel, scale=scale, attn_softcap=attn_softcap,
                             blocks_w=blocks_w, quantized=quantized)
    o, m, l = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, G, Dv), lambda b, h, w: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, w: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, w: (b, h, 0, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        # memory-roof-bound by construction: one K + one V read dominates;
        # the hint keeps XLA's scheduler from mis-costing the dispatch
        cost_estimate=pl.CostEstimate(
            flops=2 * B * W * H * (D + Dv),
            bytes_accessed=B * W * Hkv * (D + Dv)
            * k.dtype.itemsize + B * H * (D + Dv) * 4,
            transcendentals=B * W * H,
        ),
        interpret=interpret,
    )(*inputs)
    return (o.reshape(B, H, Dv), m.reshape(B, H), l.reshape(B, H))
