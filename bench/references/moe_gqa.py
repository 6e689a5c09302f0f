"""Plain float32 reference of a decoder of GQA attention and routed
experts, written from the configuration file's description and
independent of the program under test.

Per layer: RMSNorm, grouped-query attention with rotary embeddings
(rotate-half form, q head h reads kv head h // (H / Hkv), scale
1/sqrt(head_dim), causal), residual; RMSNorm, a softmax router over all
experts whose top-k experts are weighted by their softmax probabilities
(not renormalised, as the configuration files state), SwiGLU experts
(silu(x Wg) * (x Wu)) Wo, residual.  Then RMSNorm and the output head.
Weights come from bench/weights.py, drawn again from the seed, layer by
layer, so the reference never holds the whole model.

Matmuls run at ``highest`` precision.  ``fp8=True`` is the control: every
matmul with a weight takes its operands through float8 e4m3 (weights
scaled per output column, activations per row, to the format's largest
value) and computes the rest as above.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8):
    """x (..., K) @ w (K, N) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, Dh), positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, w, *, dims, fp8):
    B, S, D = h.shape
    H, Hkv, Dh = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    E, K, eps = dims["num_experts"], dims["top_k"], dims["norm_eps"]
    a = w["attn"]
    x = _rms(h, w["attn_norm"]["scale"], eps)
    q = _rope(_mm(x, a["wq"], fp8).reshape(B, S, H, Dh), dims["rope_theta"])
    k = _rope(_mm(x, a["wk"], fp8).reshape(B, S, Hkv, Dh), dims["rope_theta"])
    v = _mm(x, a["wv"], fp8).reshape(B, S, Hkv, Dh)
    q = q.reshape(B, S, Hkv, H // Hkv, Dh) * Dh ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   precision=jax.lax.Precision.HIGHEST)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    h = h + _mm(o.reshape(B, S, H * Dh), a["wo"], fp8)

    m = w["moe"]
    x = _rms(h, w["ffn_norm"]["scale"], eps).reshape(B * S, D)
    probs = jax.nn.softmax(_mm(x, m["router"], fp8), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)
    gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_w[..., None], axis=1)

    def expert(acc, xs):
        wi, wo, g = xs
        y = jax.nn.silu(_mm(x, wi[:, 0, :], fp8)) * _mm(x, wi[:, 1, :], fp8)
        return acc + g[:, None] * _mm(y, wo, fp8), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (m["wi"], m["wo"], gate.T))
    return h + out.reshape(B, S, D)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims_items, fp8):
    return jax.jit(functools.partial(_layer, dims=dict(dims_items), fp8=fp8))


@functools.lru_cache(maxsize=None)
def _head_fn(eps, fp8):
    def head(h, scale, lm_head, target):
        logits = _mm(_rms(h, scale, eps), lm_head, fp8)
        best = jnp.max(logits, -1)
        gap = best - jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
        return gap, jnp.argmax(logits, -1).astype(jnp.int32)
    return jax.jit(head)


def _batch(seqs: Sequence[Tuple[np.ndarray, Sequence[int]]]):
    """Teacher-forced inputs: prompt + served tokens but the last; the
    logits at positions len(prompt)-1 .. end predict the served tokens."""
    rows = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(s[:-1], np.int32)]) for p, s in seqs]
    S = -(-max(len(r) for r in rows) // 128) * 128
    toks = np.zeros((len(rows), S), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    return toks


def _targets(p, s, S):
    t = np.zeros((S,), np.int32)
    t[len(p) - 1:len(p) - 1 + len(s)] = s
    return t


def gaps(dims: dict, seed: int, seqs, *, control: bool = False
         ) -> List[np.ndarray]:
    """Per request, one number per served token: how far the float32
    reference's logit of that token lies below the reference's best.

    With ``control=True`` the served tokens are replaced, position by
    position, by the token the float8 reference puts first, and the gap
    of that token is returned instead."""
    toks = _batch(seqs)
    top = weights.top(dims, seed)
    h = top["embed"]["tokens"][jnp.asarray(toks)].astype(jnp.float32)
    items = tuple(sorted(dims.items()))
    h8 = h
    with jax.default_matmul_precision("highest"):
        for i in range(dims["num_layers"]):
            w = weights.layer(dims, seed, i)
            h = _layer_fn(items, False)(h, w)
            if control:
                h8 = _layer_fn(items, True)(h8, w)
            del w
        head = _head_fn(dims["norm_eps"], False)
        head8 = _head_fn(dims["norm_eps"], True)
        out = []
        for b, (p, s) in enumerate(seqs):
            lo, n = len(p) - 1, len(s)
            tgt = jnp.asarray(_targets(p, s, toks.shape[1]))
            if control:
                _, first8 = head8(h8[b], top["final_norm"]["scale"],
                                  top["lm_head"], tgt)
                tgt = first8
            gap, _ = head(h[b], top["final_norm"]["scale"], top["lm_head"],
                          tgt)
            out.append(np.asarray(gap)[lo:lo + n])
    return out
