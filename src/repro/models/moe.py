"""Mixture-of-Experts FFN.

Execution paths (numerically equivalent up to capacity drops):

  * ``moe_dense``   — masked loop over experts; O(E) compute waste; the
    reference/oracle path for unit tests and tiny smoke configs.
  * ``moe_grouped`` — single-shard capacity-bucketed grouped matmul
    (scatter tokens to (E, C, D) buckets, einsum, gather back).  This is
    the compute the Pallas ``moe_ffn`` kernel accelerates.
  * ``moe_ep_psum_local``  — expert parallelism, tokens *replicated* over
    the expert mesh axes; each shard computes its experts' contribution
    and the outputs are combined with a psum.  Robust for decode (few
    tokens per row).  Collective bytes: T*D per psum hop.
  * ``moe_ep_a2a_local``   — expert parallelism, tokens *sharded* over the
    expert axes; routed tokens are exchanged with ``lax.all_to_all``
    (capacity-bucketed), grouped-matmul'ed on the owning shard, and
    returned.  Collective bytes: ~2*T*K/M*D — the activation analogue of
    the paper's D2/D3 transfers: weights stay resident, activations move.

Gate/up projections are stored as (D, 2, F) so that sharding the 'ffn'
axis keeps the two halves aligned on every shard.

Routing follows the config: softmax top-k (mixtral/jamba/moonshot) or
sigmoid scoring with top-k renormalization (deepseek-v3 ``router_scale``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import act_fn


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

def route(cfg: ModelConfig, router_w, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, D) -> (weights (T,k) f32, idx (T,k) i32, aux_loss scalar)."""
    scores = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    if cfg.router_scale:                       # deepseek: sigmoid + renorm
        probs = jax.nn.sigmoid(scores)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
    # Switch-style load-balance loss over softmax probabilities
    sm = jax.nn.softmax(scores, axis=-1)
    T = x.shape[0]
    frac = jnp.zeros((cfg.num_experts,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (T * cfg.top_k))
    aux = cfg.num_experts * jnp.sum(frac * jnp.mean(sm, axis=0))
    return w, idx.astype(jnp.int32), aux


def expert_weights(p: Dict, dtype):
    """Dequantize int8 experts (weight-only quant, per-expert scale) to
    the compute dtype; pass-through otherwise.  On TPU the Pallas kernel
    dequantizes tile-wise in VMEM instead (ops.moe_ffn scales args)."""
    wi, wo = p["wi"], p["wo"]
    if "wi_scale" in p:
        wi = wi.astype(dtype) * p["wi_scale"].astype(dtype)[:, None, None, None]
        wo = wo.astype(dtype) * p["wo_scale"].astype(dtype)[:, None, None]
    return wi, wo


def gated_ffn(cfg: ModelConfig, wi, wo, x):
    """x: (..., D); wi: (D, 2, F); wo: (F, D)."""
    h = jnp.einsum("...d,dgf->...gf", x, wi.astype(x.dtype))
    y = act_fn(cfg.ffn_act)(h[..., 0, :]) * h[..., 1, :]
    return jnp.einsum("...f,fd->...d", y, wo.astype(x.dtype))


def expert_ffn(cfg: ModelConfig, gate, up, down_t, x):
    """One routed expert's gated FFN on its weights in the layout an
    expert span stores them (``paging.EXPERT_STORAGE_PERM``): ``gate`` and
    ``up`` are wi's halves (D, F), ``down_t`` is wo transposed (D, F).
    x: (..., D).  The same dot products as ``gated_ffn``; each projection
    is a 2-D dot on one block, so on TPU each reads its block where the
    span lies."""
    dt = x.dtype
    g = jnp.einsum("...d,df->...f", x, gate.astype(dt))
    u = jnp.einsum("...d,df->...f", x, up.astype(dt))
    y = act_fn(cfg.ffn_act)(g) * u
    return jnp.einsum("...f,df->...d", y, down_t.astype(dt))


def gated_ffn_partial_in(cfg, wi, wo, x):
    """Same as gated_ffn but wi/wo hold only an F-shard; the caller must
    psum the result over the sharded axis."""
    return gated_ffn(cfg, wi, wo, x)


# ---------------------------------------------------------------------------
# Capacity bucketing
# ---------------------------------------------------------------------------

def _bucket(dest, n_buckets: int, cap: int):
    """dest: (N,) int32 in [0, n_buckets) or -1. Returns (slot (N,), keep (N,)):
    rank of each entry within its bucket; keep = slot < cap and dest >= 0."""
    onehot = (dest[:, None] == jnp.arange(n_buckets)[None, :])
    rank = jnp.cumsum(onehot, axis=0) - 1                        # (N, nb)
    slot = jnp.sum(jnp.where(onehot, rank, 0), axis=1)
    keep = (dest >= 0) & (slot < cap)
    return slot.astype(jnp.int32), keep


def stage_bucket(dest, n_buckets: int, cap: int, groups: int = 1):
    """Cross-group routed-token staging map (module-based batching).

    dest: (N,) int32 bucket ids in [0, n_buckets) or -1, laid out
    group-major: rotation group g owns the flat positions
    [g·N/groups, (g+1)·N/groups).  Ranking runs per *(group, bucket)*
    composite bucket with per-group capacity ``cap``, so each group's
    keep/drop decisions are exactly what ``_bucket(dest_g, n_buckets,
    cap)`` would produce on that group's slice alone — the lockstep
    path's drops, reproduced inside one combined dispatch.  The staged
    slot is ``g·cap + rank``: groups occupy disjoint spans of the
    (n_buckets, groups·cap) staging buffer, so tokens of different
    groups can never mix in one bucket row (conservation is checked by
    ``stage_conservation_ok`` / the property suite).

    groups=1 degenerates to ``_bucket`` exactly."""
    N = dest.shape[0]
    assert N % groups == 0, "flat entries must split evenly over groups"
    per_g = N // groups
    g = (jnp.arange(N) // per_g).astype(jnp.int32)
    gb = jnp.where(dest >= 0, g * n_buckets + dest, -1)
    rank, keep = _bucket(gb, groups * n_buckets, cap)
    return (g * cap + rank).astype(jnp.int32), keep


def stage_conservation_ok(dest, slot, keep, n_buckets: int, cap: int,
                          groups: int = 1) -> bool:
    """Host-side invariant check for a staging index map: every kept
    entry occupies a unique staged slot inside its own group's span, and
    the kept count per (group, bucket) is exactly min(bucket size, cap)
    — i.e. tokens are conserved up to the per-group capacity drops and
    never cross group boundaries."""
    import numpy as np
    dest = np.asarray(dest)
    slot = np.asarray(slot)
    keep = np.asarray(keep, bool)
    N = dest.shape[0]
    if N % groups:
        return False
    per_g = N // groups
    g = np.arange(N) // per_g
    if keep[dest < 0].any():
        return False
    # kept slots live in their own group's span and are unique per bucket
    if not ((slot[keep] >= g[keep] * cap)
            & (slot[keep] < (g[keep] + 1) * cap)).all():
        return False
    pairs = set(zip(dest[keep].tolist(), slot[keep].tolist()))
    if len(pairs) != int(keep.sum()):
        return False
    # conservation: per (group, bucket), kept == min(routed, cap)
    for gg in range(groups):
        sl = slice(gg * per_g, (gg + 1) * per_g)
        for b in range(n_buckets):
            routed = int((dest[sl] == b).sum())
            kept = int(((dest[sl] == b) & keep[sl]).sum())
            if kept != min(routed, cap):
                return False
    return True


def grouped_ffn(cfg: ModelConfig, wi, wo, xbuf, use_kernel: bool = False,
                wi_scale=None, wo_scale=None):
    """xbuf: (E, C, D); wi: (E, D, 2, F); wo: (E, F, D) -> (E, C, D).
    int8 wi/wo + per-expert scales: the kernel path fuses the dequant into
    its tile loop; the jnp path dequantizes inline."""
    if use_kernel:
        from repro.kernels import ops
        return ops.moe_ffn(xbuf, wi, wo, wi_scale, wo_scale, act=cfg.ffn_act)
    if wi_scale is not None:
        wi = wi.astype(xbuf.dtype) * wi_scale[:, None, None, None].astype(xbuf.dtype)
        wo = wo.astype(xbuf.dtype) * wo_scale[:, None, None].astype(xbuf.dtype)
    h = jnp.einsum("ecd,edgf->ecgf", xbuf, wi.astype(xbuf.dtype))
    y = act_fn(cfg.ffn_act)(h[..., 0, :]) * h[..., 1, :]
    return jnp.einsum("ecf,efd->ecd", y, wo.astype(xbuf.dtype))


# ---------------------------------------------------------------------------
# Dense (oracle) path
# ---------------------------------------------------------------------------

def moe_dense(cfg: ModelConfig, p: Dict, x) -> Tuple[jax.Array, jax.Array]:
    """x: (T, D). Returns (out (T,D), aux_loss)."""
    w, idx, aux = route(cfg, p["router"], x)
    wi_all, wo_all = expert_weights(p, x.dtype)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(cfg.num_experts):
        y = gated_ffn(cfg, wi_all[e], wo_all[e], x)
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # (T,)
        out = out + y.astype(jnp.float32) * we[:, None]
    out = out.astype(x.dtype)
    if cfg.num_shared_experts:
        out = out + gated_ffn(cfg, p["shared"]["wi"], p["shared"]["wo"], x)
    return out, aux


# ---------------------------------------------------------------------------
# Single-shard grouped path
# ---------------------------------------------------------------------------

def moe_grouped(cfg: ModelConfig, p: Dict, x, *, capacity_factor=None,
                use_kernel: bool = False,
                token_groups: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """token_groups: module-based batching — x concatenates that many
    rotation groups' tokens (group-major).  Capacity and keep/drop
    decisions are then computed per group (``stage_bucket``), so every
    group's output is bit-identical to running it alone, while the
    expert GEMM executes once over the whole staged buffer."""
    T, D = x.shape
    NE, K = cfg.num_experts, cfg.top_k
    G = token_groups or 1
    cf = capacity_factor or cfg.capacity_factor
    cap = max(1, int((T // G) * K * cf / NE + 0.999))

    w, idx, aux = route(cfg, p["router"], x)
    flat_e = idx.reshape(-1)                                     # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    slot, keep = stage_bucket(flat_e, NE, cap, G)
    e_safe = jnp.where(keep, flat_e, 0)
    s_safe = jnp.where(keep, slot, G * cap - 1)

    xbuf = jnp.zeros((NE, G * cap, D), x.dtype)
    xbuf = xbuf.at[e_safe, s_safe].add(
        jnp.where(keep[:, None], x[flat_t], 0).astype(x.dtype))
    ybuf = grouped_ffn(cfg, p["wi"], p["wo"], xbuf, use_kernel,
                       p.get("wi_scale"), p.get("wo_scale"))
    y = ybuf[e_safe, s_safe]                                     # (T*K, D)
    y = jnp.where(keep[:, None], y, 0) * flat_w[:, None].astype(x.dtype)
    out = jnp.zeros_like(x).at[flat_t].add(y)
    if cfg.num_shared_experts:
        out = out + gated_ffn(cfg, p["shared"]["wi"], p["shared"]["wo"], x)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel bodies (to be wrapped in shard_map by distributed.sharding)
# ---------------------------------------------------------------------------

def _combined_axis_index(axis_names):
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _combined_axis_size(axis_names):
    m = 1
    for a in axis_names:
        m *= jax.lax.axis_size(a)
    return m


def moe_ep_psum_local(cfg: ModelConfig, p_local: Dict, x, *, expert_axes,
                      capacity_factor=None, use_kernel: bool = False,
                      shared_sharded: bool = False, ffn_axes=()):
    """Tokens replicated over expert_axes (+ffn_axes); p_local holds the
    local expert slice wi (E_loc, D, 2, F_loc), wo (E_loc, F_loc, D);
    router replicated.  With ffn_axes set, each expert's FFN dim is also
    sharded (2D stationary weights) and the output psum covers both axis
    groups — decode then moves only (T, D)-sized activations while every
    weight stays resident on its shard.  x: (T, D)."""
    T, D = x.shape
    NE, K = cfg.num_experts, cfg.top_k
    M = _combined_axis_size(expert_axes)
    E_loc = NE // M
    my = _combined_axis_index(expert_axes)
    cf = capacity_factor or cfg.capacity_factor
    cap_e = max(1, int(T * K * cf / NE + 0.999))

    w, idx, aux = route(cfg, p_local["router"], x)
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    local_e = flat_e - my * E_loc
    mine = (local_e >= 0) & (local_e < E_loc)
    dest = jnp.where(mine, local_e, -1)
    slot, keep = _bucket(dest, E_loc, cap_e)
    e_safe = jnp.where(keep, dest, 0)
    s_safe = jnp.where(keep, slot, cap_e - 1)

    xbuf = jnp.zeros((E_loc, cap_e, D), x.dtype).at[e_safe, s_safe].add(
        jnp.where(keep[:, None], x[flat_t], 0).astype(x.dtype))
    ybuf = grouped_ffn(cfg, p_local["wi"], p_local["wo"], xbuf, use_kernel,
                       p_local.get("wi_scale"), p_local.get("wo_scale"))
    y = jnp.where(keep[:, None], ybuf[e_safe, s_safe], 0)
    y = y * flat_w[:, None].astype(x.dtype)
    out = jnp.zeros_like(x).at[flat_t].add(y)
    reduce_axes = tuple(expert_axes) + tuple(ffn_axes)
    Mr = _combined_axis_size(reduce_axes)
    if cfg.num_shared_experts:
        sh = gated_ffn(cfg, p_local["shared"]["wi"], p_local["shared"]["wo"], x)
        if shared_sharded or ffn_axes:
            # partial-F contribution folds into the psum, but it is
            # replicated across expert_axes — pre-divide by that factor
            out = out + sh / (M if ffn_axes else 1)
        else:
            out = out + sh / Mr                   # fully replicated
    out = jax.lax.psum(out, reduce_axes)
    return out, aux


def moe_ep_a2a_local(cfg: ModelConfig, p_local: Dict, x, *, expert_axes,
                     capacity_factor=None, use_kernel: bool = False,
                     shared_sharded: bool = False):
    """Tokens *sharded* over expert_axes (x is the local token slice).
    Exchanges routed tokens via all_to_all.  x: (T_loc, D)."""
    T, D = x.shape
    NE, K = cfg.num_experts, cfg.top_k
    M = _combined_axis_size(expert_axes)
    E_loc = NE // M
    cf = capacity_factor or cfg.capacity_factor
    cap = max(1, int(T * K * cf / M + 0.999))            # per src->dst lane
    cap_e = max(1, int(M * cap * cf / E_loc + 0.999))    # per local expert

    w, idx, aux = route(cfg, p_local["router"], x)
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    dest = flat_e // E_loc
    slot, keep = _bucket(dest, M, cap)
    d_safe = jnp.where(keep, dest, 0)
    s_safe = jnp.where(keep, slot, cap - 1)

    send_x = jnp.zeros((M, cap, D), x.dtype).at[d_safe, s_safe].add(
        jnp.where(keep[:, None], x[flat_t], 0).astype(x.dtype))
    send_le = jnp.full((M, cap), -1, jnp.int32).at[d_safe, s_safe].max(
        jnp.where(keep, (flat_e % E_loc).astype(jnp.int32), -1))

    recv_x = jax.lax.all_to_all(send_x, expert_axes, 0, 0, tiled=True)
    recv_le = jax.lax.all_to_all(send_le, expert_axes, 0, 0, tiled=True)

    rx = recv_x.reshape(M * cap, D)
    rle = recv_le.reshape(M * cap)
    slot2, keep2 = _bucket(rle, E_loc, cap_e)
    e2 = jnp.where(keep2, rle, 0)
    s2 = jnp.where(keep2, slot2, cap_e - 1)
    xbuf = jnp.zeros((E_loc, cap_e, D), x.dtype).at[e2, s2].add(
        jnp.where(keep2[:, None], rx, 0))
    ybuf = grouped_ffn(cfg, p_local["wi"], p_local["wo"], xbuf, use_kernel,
                       p_local.get("wi_scale"), p_local.get("wo_scale"))
    ry = jnp.zeros((M * cap, D), x.dtype).at[jnp.arange(M * cap)].set(
        jnp.where(keep2[:, None], ybuf[e2, s2], 0)).reshape(M, cap, D)

    back = jax.lax.all_to_all(ry, expert_axes, 0, 0, tiled=True)
    y = back[d_safe, s_safe]
    y = jnp.where(keep[:, None], y, 0) * flat_w[:, None].astype(x.dtype)
    out = jnp.zeros_like(x).at[flat_t].add(y)
    if cfg.num_shared_experts:
        sh = gated_ffn(cfg, p_local["shared"]["wi"], p_local["shared"]["wo"], x)
        if shared_sharded:
            sh = jax.lax.psum(sh, expert_axes)
        out = out + sh
    aux = jax.lax.pmean(aux, expert_axes)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-granular paged path (two-phase layer step)
# ---------------------------------------------------------------------------

def activated_experts(idx, num_experts: int, max_active: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the routed expert set: idx (T, K) -> (sel, index_map, n_act).

    sel (max_active,): activated expert ids in ascending order, padded with
    0 beyond n_act (padding slots never receive tokens — the index map only
    targets real compact slots — and the paged fetch reads nothing for
    them).  index_map (E,): expert id → compact slot, -1 if not
    activated.  ``max_active`` must be ≥ min(E, T*K) for exactness; the
    callers derive it from static shapes so this always holds."""
    hit = jnp.zeros((num_experts,), bool).at[idx.reshape(-1)].set(True)
    index_map = jnp.where(hit, jnp.cumsum(hit) - 1, -1).astype(jnp.int32)
    sel = jnp.nonzero(hit, size=max_active, fill_value=0)[0].astype(jnp.int32)
    return sel, index_map, jnp.sum(hit).astype(jnp.int32)


def _weighted_entry(cfg: ModelConfig, p: Dict, x, w, idx, sel):
    """apply(a, leaves, acc) for ``fetch_experts.each``: entry a's
    routing-weighted expert output added to the float32 accumulator.
    ``leaves`` are the expert's leaves in their storage layout
    (``paging.ExpertManifest.leaf_blocks``).  Entries arrive in ascending
    activated-expert order, so the sum matches ``moe_dense`` bit-for-bit
    up to ±0 (the experts it skips contribute exactly zero there)."""
    dt = x.dtype

    def apply(a, leaves, acc):
        with jax.named_scope("moe_ffn"):
            gate, up = leaves["wi"]
            (down_t,) = leaves["wo"]
            if "wi_scale" in p:
                # int8 dequant scales live in the shared span (see
                # paging.EXPERT_LEAF_NAMES)
                si = p["wi_scale"][sel[a]].astype(dt)
                so = p["wo_scale"][sel[a]].astype(dt)
                gate, up = gate.astype(dt) * si, up.astype(dt) * si
                down_t = down_t.astype(dt) * so
            y = expert_ffn(cfg, gate, up, down_t, x)
            we = jnp.sum(jnp.where(idx == sel[a], w, 0.0), axis=-1)  # (T,)
            return acc + y.astype(jnp.float32) * we[:, None]

    return apply


def _grouped_subset(cfg: ModelConfig, ep: Dict, x, w, idx, index_map,
                    capacity_factor=None, use_kernel: bool = False,
                    token_groups: Optional[int] = None):
    """Capacity-bucketed grouped compute on a compacted subset.  Capacity
    and keep/drop decisions use the FULL expert count (cfg.num_experts),
    so drops are identical to ``moe_grouped`` on the full set.

    token_groups: module-based batching — x concatenates that many
    rotation groups' tokens (group-major) and the staging buffer holds a
    disjoint ``cap``-wide span per (group, expert) (``stage_bucket``):
    per-group capacity, per-group drops, one grouped GEMM per activated
    expert over the whole accumulation window."""
    T, D = x.shape
    NE, K = cfg.num_experts, cfg.top_k
    A = ep["wi"].shape[0]
    G = token_groups or 1
    cf = capacity_factor or cfg.capacity_factor
    cap = max(1, int((T // G) * K * cf / NE + 0.999))

    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = w.reshape(-1)
    dest = index_map[flat_e]                   # compact slot, always >= 0
    slot, keep = stage_bucket(dest, A, cap, G)
    e_safe = jnp.where(keep, dest, 0)
    s_safe = jnp.where(keep, slot, G * cap - 1)

    xbuf = jnp.zeros((A, G * cap, D), x.dtype)
    xbuf = xbuf.at[e_safe, s_safe].add(
        jnp.where(keep[:, None], x[flat_t], 0).astype(x.dtype))
    ybuf = grouped_ffn(cfg, ep["wi"], ep["wo"], xbuf, use_kernel,
                       ep.get("wi_scale"), ep.get("wo_scale"))
    y = ybuf[e_safe, s_safe]
    y = jnp.where(keep[:, None], y, 0) * flat_w[:, None].astype(x.dtype)
    return jnp.zeros_like(x).at[flat_t].add(y)


def moe_paged(cfg: ModelConfig, p: Dict, x, *, fetch_experts,
              policy=None, max_active: Optional[int] = None,
              token_groups: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Two-phase MoE step for expert-granular paged weights: run the
    router FIRST, then read only the activated experts' spans (resident
    spans in place from the device pool, misses from the host store,
    nothing for padding entries) through ``fetch_experts`` (a
    ``models.model.ExpertFetch``):

      * ``each(sel, n_act, apply, acc)`` applies every activated expert
        straight from its span blocks and sums the weighted outputs —
        the serving path: one expert's weights are live at a time and no
        subset is ever stacked;
      * ``stacked(sel, n_act)`` stacks the subset in the model layout
        ({wi (A, D, 2, F), wo (A, F, D)}) for the policies whose compute
        needs one operand for all experts: ``moe_impl="grouped"`` and
        the Pallas ``moe_ffn`` kernel (``use_kernels``).

    x: (T, D).  Returns (out, aux_loss, counts (E,) int32 — tokens routed
    to each expert, the residency EWMA's observation — and the fetch's
    ``reads``: [host-store reads, pool reads], one per activated expert,
    none for padding).  Numerics match moe_dense / moe_grouped on the
    full expert set (skipped experts contribute exactly zero there), so
    greedy transcripts are bit-identical to whole-layer streaming.

    token_groups=G (module-based batching): x concatenates G rotation
    groups' tokens group-major.  The activated set (and the span fetch)
    then covers the UNION of the groups' routed experts — each read span
    serves every group's staged tokens in one accumulation window —
    while per-group numerics stay bit-identical to G separate calls
    (the dense path adds the extra experts at exactly ±0;
    ``_grouped_subset`` buckets with per-group capacity).  counts is
    then (G, E) so the host residency cache can book per-window traffic
    yet keep per-group router-ahead predictions."""
    T, D = x.shape
    NE, K = cfg.num_experts, cfg.top_k
    A = max_active if max_active is not None else min(NE, T * K)
    with jax.named_scope("router"):
        w, idx, aux = route(cfg, p["router"], x)
        flat_e = idx.reshape(-1)
        if token_groups:
            G = token_groups
            g_flat = (jnp.arange(T * K) // (K * (T // G))).astype(jnp.int32)
            counts = jnp.zeros((G, NE), jnp.int32).at[g_flat, flat_e].add(1)
        else:
            counts = jnp.zeros((NE,), jnp.int32).at[flat_e].add(1)
        sel, index_map, n_act = activated_experts(idx, NE, A)
    if policy is not None and policy.moe_impl == "grouped":
        ep, reads = fetch_experts.stacked(sel, n_act)
        with jax.named_scope("moe_ffn"):
            if "wi_scale" in p:
                ep = dict(ep, wi_scale=p["wi_scale"][sel],
                          wo_scale=p["wo_scale"][sel])
            out = _grouped_subset(cfg, ep, x, w, idx, index_map,
                                  use_kernel=policy.use_kernels,
                                  token_groups=token_groups)
    else:
        acc, reads = fetch_experts.each(
            sel, n_act, _weighted_entry(cfg, p, x, w, idx, sel),
            jnp.zeros(x.shape, jnp.float32))
        out = acc.astype(x.dtype)
    if cfg.num_shared_experts:
        with jax.named_scope("moe_ffn"):
            out = out + gated_ffn(cfg, p["shared"]["wi"], p["shared"]["wo"],
                                  x)
    return out, aux, counts, reads


def moe_apply_paged(cfg: ModelConfig, p: Dict, x3, fetch_experts,
                    policy=None, token_groups: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(B, S, D) wrapper around moe_paged (the expert-granular analogue of
    moe_apply).  With token_groups, B must be G·ubatch (decode windows)
    so the flat group-major layout holds."""
    B, S, D = x3.shape
    out, aux, counts, reads = moe_paged(cfg, p, x3.reshape(B * S, D),
                                        fetch_experts=fetch_experts,
                                        policy=policy,
                                        token_groups=token_groups)
    return out.reshape(B, S, D), aux, counts, reads


def moe_apply(cfg: ModelConfig, p: Dict, x3, policy=None,
              token_groups: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch on the execution policy. x3: (B, S, D)."""
    B, S, D = x3.shape
    if policy is not None and policy.moe_fn is not None:
        out, aux = policy.moe_fn(cfg, p, x3)
        return out, aux
    x = x3.reshape(B * S, D)
    if policy is not None and policy.moe_impl == "grouped":
        out, aux = moe_grouped(cfg, p, x, use_kernel=policy.use_kernels,
                               token_groups=token_groups)
    else:
        out, aux = moe_dense(cfg, p, x)
    return out.reshape(B, S, D), aux
