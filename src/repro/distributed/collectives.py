"""Distributed collectives built on shard_map.

`make_seq_sharded_attn` is the TPU adaptation of the paper's CPU attention
(DESIGN.md §2): the KV cache is sharded along the *sequence* axis across
chips; at each decode step the (tiny) per-token q is broadcast, every chip
computes attention partials against its local KV pages, and partials are
combined with a log-sum-exp-weighted psum.  Wire bytes per step are
O(batch × heads × head_dim) — independent of context length — exactly the
paper's "move the hidden state, not the KV cache".
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.attention import attention_partials

def lse_combine(o, m, l, axes):
    """Combine attention partials across mesh `axes`.
    o: (B,H,Dv) f32 unnormalized; m, l: (B,H) f32."""
    m_glob = jax.lax.pmax(m, axes)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axes)
    o_glob = jax.lax.psum(o * corr[..., None], axes)
    return o_glob / jnp.maximum(l_glob[..., None], 1e-30)


def make_seq_sharded_attn(mesh: Mesh, dp_axes: Tuple[str, ...],
                          kv_axes: Tuple[str, ...]):
    """Returns fn(q, k, v, valid, *, scale, attn_softcap) -> (B,H,Dv).

    q: (B,H,D) sharded over dp_axes on B, replicated over kv_axes.
    k/v: (B,W,Hkv,D*) with W sharded over kv_axes.
    valid: (B,W) bool, same sharding as the KV sequence dim.
    """
    dp = dp_axes if dp_axes else None

    def body(q, k, v, valid, *, scale, attn_softcap):
        o, m, l = attention_partials(q, k, v, valid, scale=scale,
                                     attn_softcap=attn_softcap)
        out = lse_combine(o, m, l, kv_axes)
        return out.astype(q.dtype)

    def fn(q, k, v, valid, *, scale, attn_softcap):
        sm = jax.shard_map(
            functools.partial(body, scale=scale, attn_softcap=attn_softcap),
            mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, kv_axes, None, None),
                      P(dp, kv_axes, None, None), P(dp, kv_axes)),
            out_specs=P(dp, None, None), check_vma=False)
        return sm(q, k, v, valid)

    return fn


def make_moe_shard_fn(mesh: Mesh, cfg, *, variant: str,
                      dp_axes: Tuple[str, ...], expert_axes: Tuple[str, ...],
                      token_axis: str = None, use_kernels: bool = False,
                      shared_sharded: bool = False,
                      capacity_factor: float = None,
                      ffn_axes: Tuple[str, ...] = ()):
    """Wrap a moe_ep_* body in shard_map.

    variant "ep_psum": tokens replicated over expert_axes (x spec keeps
      only dp on batch); output psum'ed.  With `ffn_axes`, each expert's
      FFN dim is additionally sharded over those axes (2D stationary
      weights for decode) and the psum covers both groups.
    variant "ep_a2a": tokens additionally sharded over expert_axes —
      batch over dp, sequence over `token_axis` (defaults to the last
      expert axis); routed tokens exchanged with all_to_all.
    """
    from repro.models import moe as moe_mod
    dp = dp_axes if dp_axes else None
    NE = cfg.num_experts

    # per-leaf specs for the (layer-sliced) moe param subtree
    e_ax = expert_axes
    f_ax = tuple(ffn_axes) or None
    p_specs = {"router": P(None, None),
               "wi": P(e_ax, None, None, f_ax),
               "wo": P(e_ax, f_ax, None)}
    if cfg.expert_dtype == "int8":
        p_specs["wi_scale"] = P(e_ax)
        p_specs["wo_scale"] = P(e_ax)
    if cfg.num_shared_experts:
        p_specs["shared"] = {"wi": P(None, None, f_ax), "wo": P(f_ax, None)}

    if variant == "ep_psum":
        x_spec = P(dp, None, None)
        body = functools.partial(moe_mod.moe_ep_psum_local, cfg,
                                 expert_axes=expert_axes,
                                 use_kernel=use_kernels,
                                 capacity_factor=capacity_factor,
                                 ffn_axes=tuple(ffn_axes),
                                 shared_sharded=False)
    elif variant == "ep_a2a":
        tok_ax = token_axis or expert_axes[-1]
        seq_axes = tuple(a for a in expert_axes if a != "data") or (tok_ax,)
        # batch over dp(+data if data is an expert axis handled below)
        if "data" in expert_axes:
            # tokens must be sharded over ALL expert axes: batch carries
            # 'data' (it already does via dp) and the sequence carries the
            # rest ('model')
            x_spec = P(dp, tuple(a for a in expert_axes if a != "data") or None,
                       None)
        else:
            x_spec = P(dp, expert_axes, None)
        body = functools.partial(moe_mod.moe_ep_a2a_local, cfg,
                                 expert_axes=expert_axes,
                                 use_kernel=use_kernels,
                                 capacity_factor=capacity_factor,
                                 shared_sharded=False)
    else:
        raise ValueError(variant)

    def wrapped(p_local, x2d):
        out, aux = body(p_local, x2d)
        return out, aux

    all_axes = tuple(mesh.axis_names)

    def fn(cfg_, p, x3):
        B, S, D = x3.shape

        def body3(p_local, x3l):
            b, s, _ = x3l.shape
            out, aux = wrapped(p_local, x3l.reshape(b * s, D))
            aux = jax.lax.pmean(aux, all_axes)   # replicated metric
            return out.reshape(b, s, D), aux

        sm = jax.shard_map(
            body3, mesh=mesh,
            in_specs=(p_specs, x_spec),
            out_specs=(x_spec, P()), check_vma=False)
        return sm(p, x3)

    return fn
