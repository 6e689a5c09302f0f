"""Everything the harness knows of a decoder of GQA attention and routed
experts (Mixtral): the sizes it needs, its seeded weights and the
engine's tree built from them, the work a token costs, and a plain
float32 reference written from the configuration file's description and
independent of the program under test.  bench/spec.py loads it by the
name in the configuration file's ``reference`` and checks that it
defines every one of ``spec.REFERENCE_EXPORTS``.

Weights (per layer): ``attn`` {wq (D, H*Dh), wk, wv (D, Hkv*Dh), wo
(H*Dh, D)}, ``attn_norm``/``ffn_norm`` {scale (D,)}, ``moe`` {router
(D, E), wi (E, D, 2, F) with gate at [..., 0, :] and up at [..., 1, :],
wo (E, F, D)}; top level: ``embed`` {tokens (V, D)}, ``final_norm``
{scale (D,)}, ``lm_head`` (D, V).  Every layer is the engine's one-layer
period, so the layers stack into ``blocks.p0``.

Work: model FLOPs count one multiply-add as 2 operations and take only
what a token needs: its projections, its router, its top-k experts,
attention over the context it sees, and the output head for a generated
token.  The paged-decode kernel's least work is one query row per
sequence against the K and V of that sequence's live context.

Reference, per layer: RMSNorm, grouped-query attention with rotary
embeddings (rotate-half form, q head h reads kv head h // (H / Hkv),
scale 1/sqrt(head_dim), causal), residual; RMSNorm, a softmax router over
all experts whose top-k experts are weighted by their softmax
probabilities (not renormalised, as the configuration files state),
SwiGLU experts (silu(x Wg) * (x Wu)) Wo, residual.  Then RMSNorm and the
output head.  Weights are drawn again from the seed, layer by layer, so
the reference never holds the whole model.

Matmuls run at ``highest`` precision.  ``fp8=True`` is the control: every
matmul with a weight takes its operands through float8 e4m3 (weights
scaled per output column, activations per row, to the format's largest
value) and computes the rest as above.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# the Pallas call shows in the trace as "paged_gqa_decode_fused.<n>"
DECODE_KERNEL = "paged_gqa"


# ------------------------------------------------------------------ sizes

def dims(cfg) -> Dict[str, object]:
    """The sizes the weights, counts and reference need."""
    return {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "num_experts": cfg.num_experts, "top_k": cfg.top_k,
            "num_layers": cfg.num_layers, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps}


# ---------------------------------------------------------------- weights

def _draw_layer(key, *, D, H, Hkv, Dh, F, E):
    k = jax.random.split(key, 9)
    normal, scale = weights.normal, weights.scale
    return {
        "attn": {"wq": normal(k[0], (D, H * Dh), D),
                 "wk": normal(k[1], (D, Hkv * Dh), D),
                 "wv": normal(k[2], (D, Hkv * Dh), D),
                 "wo": normal(k[3], (H * Dh, D), H * Dh)},
        "attn_norm": {"scale": scale(k[4], D)},
        "ffn_norm": {"scale": scale(k[5], D)},
        "moe": {"router": normal(k[6], (D, E), D),
                "wi": normal(k[7], (E, D, 2, F), D),
                "wo": normal(k[8], (E, F, D), F)},
    }


def _draw_top(key, *, D, V):
    k = jax.random.split(key, 3)
    return {"embed": {"tokens": weights.normal(k[0], (V, D), D)},
            "final_norm": {"scale": weights.scale(k[1], D)},
            "lm_head": weights.normal(k[2], (D, V), D)}


@functools.lru_cache(maxsize=None)
def _draw_layer_fn(D, H, Hkv, Dh, F, E):
    return jax.jit(functools.partial(_draw_layer, D=D, H=H, Hkv=Hkv, Dh=Dh,
                                     F=F, E=E))


@functools.lru_cache(maxsize=None)
def _draw_top_fn(D, V):
    return jax.jit(functools.partial(_draw_top, D=D, V=V))


def layer(dims: dict, seed: int, index: int) -> dict:
    """Weights of layer ``index``, on the device."""
    key = jax.random.fold_in(weights.base_key(seed), index + 1)
    return _draw_layer_fn(dims["d_model"], dims["num_heads"],
                          dims["num_kv_heads"], dims["head_dim"],
                          dims["d_ff"], dims["num_experts"])(key)


def top(dims: dict, seed: int) -> dict:
    """Embedding, final norm and output head, on the device."""
    key = jax.random.fold_in(weights.base_key(seed), 0)
    return _draw_top_fn(dims["d_model"], dims["vocab_size"])(key)


def engine_params(cfg, dims: dict, seed: int) -> dict:
    """The engine's parameter tree: layers drawn on the device one at a
    time and kept in host memory, stacked into the one-layer period
    ``blocks.p0``; the top level on the device."""
    layers = [jax.device_get(layer(dims, seed, i))
              for i in range(dims["num_layers"])]
    params = dict(top(dims, seed))
    params["blocks"] = {"p0": jax.tree.map(lambda *xs: np.stack(xs),
                                           *layers)}
    return params


# ------------------------------------------------------------------- work

def layer_flops(d: dict, ctx: int) -> int:
    """One token through one layer, attending over ``ctx`` positions."""
    D, H, Hkv, Dh = d["d_model"], d["num_heads"], d["num_kv_heads"], \
        d["head_dim"]
    proj = 2 * D * (H * Dh + 2 * Hkv * Dh) + 2 * H * Dh * D
    attn = 4 * H * Dh * ctx                      # q.k and p.v
    router = 2 * D * d["num_experts"]
    experts = d["top_k"] * 2 * 3 * D * d["d_ff"]
    return proj + attn + router + experts


def head_flops(d: dict) -> int:
    return 2 * d["d_model"] * d["vocab_size"]


def prefill_flops(d: dict, prompt_len: int) -> int:
    """A prompt, causal (position i sees i + 1 positions), and the head
    at its last position."""
    n = prompt_len
    per_layer = n * layer_flops(d, 0) + 4 * d["num_heads"] * d["head_dim"] \
        * n * (n + 1) // 2
    return d["num_layers"] * per_layer + head_flops(d)


def decode_flops(d: dict, ctx: int) -> int:
    """One generated token whose query sees ``ctx`` positions."""
    return d["num_layers"] * layer_flops(d, ctx) + head_flops(d)


def paged_decode_work(d: dict, ctx: int, kv_bytes: int = 2):
    """(flops, bytes) of one sequence's decode attention in one layer:
    read its q, the K and V of ``ctx`` positions, write its output."""
    H, Hkv, Dh = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    flops = 4 * H * Dh * ctx
    nbytes = 2 * ctx * Hkv * Dh * kv_bytes + 2 * H * Dh * kv_bytes
    return flops, nbytes


def decode_kernel_work(d: dict, ctx: int):
    """(flops, bytes) of the paged-decode kernel for one generated token
    whose query sees ``ctx`` positions: every layer runs it once."""
    flops, nbytes = paged_decode_work(d, ctx)
    return d["num_layers"] * flops, d["num_layers"] * nbytes


# -------------------------------------------------------------- reference


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
    w_top = top(dims, seed)
    h = w_top["embed"]["tokens"][jnp.asarray(toks)].astype(jnp.float32)
    items = tuple(sorted(dims.items()))
    h8 = h
    with jax.default_matmul_precision("highest"):
        for i in range(dims["num_layers"]):
            w = layer(dims, seed, i)
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
                _, first8 = head8(h8[b], w_top["final_norm"]["scale"],
                                  w_top["lm_head"], tgt)
                tgt = first8
            gap, _ = head(h[b], w_top["final_norm"]["scale"],
                          w_top["lm_head"], tgt)
            out.append(np.asarray(gap)[lo:lo + n])
    return out
