"""Model assembly: embedding → (prologue + scanned periodic blocks) → norm →
unembed, for every assigned architecture (dense / MoE / SSM / hybrid /
enc-dec / vlm-prefix).

The periodic layer stack is executed with ``jax.lax.scan`` over *periods*
(param stacks built by models.params), so the lowered HLO is O(period
length), independent of depth — this is what keeps the 512-device dry-run
compiles of 61-layer DeepSeek-V3 and 72-layer Jamba tractable.

Execution strategy (which MoE path, which sharded-attention combine, remat)
is injected through an `ExecPolicy` so the same model code runs on a laptop
CPU and on a 512-chip mesh.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import kvcache
from repro.models.attention import attn_forward, gqa_forward
from repro.models.common import (act_fn, apply_norm, sinusoidal_positions,
                                 softcap)
from repro.models.mamba import mamba_forward
from repro.models.moe import gated_ffn, moe_apply, moe_apply_paged


@dataclass
class ExecPolicy:
    """How to execute (not what to compute)."""
    moe_impl: str = "dense"               # dense | grouped
    moe_fn: Optional[Callable] = None     # overrides moe_impl when set
    attn_fn: Optional[Callable] = None    # sharded decode-attention combine
    use_kernels: bool = False
    paged_attn_impl: str = "auto"         # paged-decode kernel dispatch:
    # auto (Pallas on TPU, dense-view ref elsewhere) | pallas | interpret
    # | ref — see kernels.ops.paged_gqa_decode
    remat: bool = False
    scan_unroll: int = 1


class ExpertFetch(NamedTuple):
    """One layer's router-gated expert fetch (``_ExpertCtx.make_fetch``),
    the contract ``moe.moe_paged`` calls."""
    each: Callable     # (sel, n_act, apply, acc) -> (acc, reads (2,))
    stacked: Callable  # (sel, n_act) -> ({wi, wo} (A, ...), reads (2,))


@dataclass
class _ExpertCtx:
    """Scan-invariant state for one group's expert-granular paged weights:
    the host span store, its manifest, and (optionally) the device
    residency pool + (layer, expert) → slot map snapshot."""
    pages: Any                            # (L, E, *span_shape) host store
    manifest: Any                         # paging.ExpertManifest
    pool: Optional[Any] = None            # (slots, *span_shape) device
    resident_map: Optional[Any] = None    # (L, E) int32, -1 = host only

    def make_fetch(self, layer) -> ExpertFetch:
        """Bind the traced layer index.  ``each(sel, n_act, apply, acc)
        -> (acc, reads)``: for each entry ``a`` of the activated set
        ``sel`` (A,), one ``lax.switch`` runs
        ``acc = apply(a, leaves, acc)`` with ``leaves`` the expert's
        leaves in their storage layout, by name
        (``paging.ExpertManifest.leaf_blocks``), read where they lie:

          * a resident span straight from its pool slot;
          * a miss sliced from the store on its own and moved to device
            memory explicitly, one copy per expert (on TPU the store lives
            in pinned host memory, so that move IS the host→device
            transfer; a gather on a host operand does not compile);
          * a padding entry (``a >= n_act``) reads nothing and leaves
            ``acc`` as it is.

        ``apply`` returns something ``acc``-shaped, so no span crosses a
        branch boundary and nothing is stacked unless ``apply`` stacks it.
        reads (2,) int32 counts the branches taken: [spans read from the
        host store, spans read from the pool].

        ``stacked(sel, n_act)`` is ``each`` with an ``apply`` that stacks
        the spans (padding entries stay zero) and rebuilds the (A, ...)
        subset in the model layout, for the compute that needs it."""
        from repro.core import offload as _offload
        from repro.core import paging as _paging

        em = self.manifest

        def each(sel, n_act, apply, acc):
            L, E = self.pages.shape[:2]
            # (layer, expert) -> one major index: a host slice may cut
            # only the most major dimension
            store = self.pages.reshape((L * E,) + em.span_shape)

            def from_host(a, acc):
                with jax.named_scope("expert_fetch"):
                    span = _offload.device_operand(
                        jax.lax.dynamic_index_in_dim(
                            store, layer * E + sel[a], 0, keepdims=False))
                return apply(a, em.leaf_blocks(lambda i: span[i]), acc)

            live = jnp.arange(sel.shape[0]) < n_act
            if self.pool is None:
                resident = jnp.zeros_like(live)
                readers = (from_host,)
            else:
                # one block per index: each dot reads its slot's block in
                # place (a dynamic slice of the whole slot is materialized)
                blocks = self.pool.reshape((-1,) + tuple(em.block_shape))
                slots = self.resident_map[layer, sel]
                resident = slots >= 0

                def from_pool(a, acc):
                    with jax.named_scope("expert_fetch"):
                        leaves = em.leaf_blocks(
                            lambda i: jax.lax.dynamic_index_in_dim(
                                blocks, slots[a] * em.blocks + i, 0,
                                keepdims=False))
                    return apply(a, leaves, acc)

                readers = (from_host, from_pool)
            # branch 0: a padding entry; 1: the host store; 2: the pool
            branch = jnp.where(live, 1 + resident.astype(jnp.int32), 0)
            for a in range(sel.shape[0]):
                acc = jax.lax.switch(branch[a], (lambda acc: acc,) + tuple(
                    functools.partial(f, a) for f in readers), acc)
            n_pool = jnp.sum(live & resident, dtype=jnp.int32)
            n_host = jnp.sum(live, dtype=jnp.int32) - n_pool
            return acc, jnp.stack([n_host, n_pool])

        def stacked(sel, n_act):
            def put(a, leaves, acc):          # the span's blocks in order
                return acc.at[a].set(jnp.stack(
                    [b for e in em.leaves for b in leaves[e.path[-1]]]))

            spans, reads = each(sel, n_act, put, jnp.zeros(
                sel.shape + em.span_shape, self.pages.dtype))
            return _paging.unflatten_expert_span(spans, em), reads

        return ExpertFetch(each, stacked)


# ---------------------------------------------------------------------------
# FFN (dense)
# ---------------------------------------------------------------------------

def dense_ffn(cfg: ModelConfig, p: Dict, x):
    if cfg.ffn_act == "gelu_mlp":
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(x.dtype))
        h = act_fn("gelu_mlp")(h + p["bi"].astype(x.dtype))
        return jnp.einsum("...f,fd->...d", h, p["wo"].astype(x.dtype)) \
            + p["bo"].astype(x.dtype)
    return gated_ffn(cfg, p["wi"], p["wo"], x)


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def block_apply(cfg: ModelConfig, spec: LayerSpec, p: Dict, x, *,
                positions, cache: Optional[Dict], mode: str,
                pos: Optional[jax.Array], enc_out: Optional[jax.Array],
                xattn_cache: Optional[Dict], policy: Optional[ExecPolicy],
                causal: bool = True, expert_fetch=None,
                token_groups: Optional[int] = None):
    """Returns (x, new_cache, new_xattn_cache, aux_loss, expert).

    With ``expert_fetch`` set (expert-granular paged weights), the MoE FFN
    runs the two-phase step: router first, then only the activated
    experts' spans are read and applied; ``expert`` is (counts (E,), reads
    (2,)): the routing, so the host-side residency cache can learn
    popularity and account hits/misses, and the spans the fetch read
    from the host store and from the pool.  Otherwise expert is None.

    token_groups=G (module-based batching): the batch concatenates G
    rotation groups.  Attention/norms are per-row so they are untouched;
    the MoE FFN stages the G groups' routed tokens into one cross-group
    buffer so each expert span is read once per window, and counts
    becomes (G, E)."""
    aux = jnp.float32(0.0)
    ecounts = None
    new_cache, new_x = cache, xattn_cache

    if spec.kind == "mamba":
        h = apply_norm(cfg, p.get("mamba_norm", {}), x)
        y, new_cache = mamba_forward(cfg, p["mamba"], h, cache=cache, mode=mode)
        x = x + y
    else:
        h = apply_norm(cfg, p.get("attn_norm", {}), x)
        with jax.named_scope("attention"):
            y, new_cache = attn_forward(
                cfg, spec, p["attn"], h, positions, cache=cache, mode=mode,
                pos=pos, sharded_fn=policy.attn_fn if policy else None,
                paged_impl=policy.paged_attn_impl if policy else "auto",
                **({} if causal else {"causal": False}))
        if cfg.post_block_norm:
            y = apply_norm(cfg, p["post_attn_norm"], y)
        x = x + y

    if spec.cross_attn:
        h = apply_norm(cfg, p["xattn_norm"], x)
        if mode == "decode":
            kv = (xattn_cache["k"], xattn_cache["v"])
        else:
            # build cross KV from encoder output, persist for decode
            B, Se, _ = enc_out.shape
            Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
            k = jnp.einsum("bse,ef->bsf", enc_out,
                           p["xattn"]["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bse,ef->bsf", enc_out,
                           p["xattn"]["wv"].astype(enc_out.dtype))
            kv = (k.reshape(B, Se, Hkv, Dh), v.reshape(B, Se, Hkv, Dh))
            new_x = {"k": kv[0], "v": kv[1]}
        y, _ = gqa_forward(cfg, LayerSpec(), p["xattn"], h, positions,
                           cache=None, mode="full", kv_override=kv)
        x = x + y

    if spec.ffn:
        h = apply_norm(cfg, p.get("ffn_norm", {}), x)
        if spec.moe:
            if expert_fetch is not None:
                y, aux, counts, reads = moe_apply_paged(
                    cfg, p["moe"], h, expert_fetch, policy,
                    token_groups=token_groups)
                ecounts = (counts, reads)
            else:
                y, aux = moe_apply(cfg, p["moe"], h, policy,
                                   token_groups=token_groups)
        else:
            y = dense_ffn(cfg, p["ffn"], h)
        if cfg.post_block_norm:
            y = apply_norm(cfg, p["post_ffn_norm"], y)
        x = x + y
    return x, new_cache, new_x, aux, ecounts


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _run_group(cfg, specs, stacked_p, x, *, n_steps, positions, cache_group,
               mode, pos, enc_out, xattn_group, policy, causal=True,
               manifests=None, expert_ctx=None, token_groups=None):
    """Scan `n_steps` times over a group of layer specs whose params (and
    caches) are stacked on the leading axis.  When `manifests` maps a
    group key to a PageManifest, that group's xs entry is a page span
    (paged weights, paper Appendix A.1) rebuilt in-scan.  When
    `expert_ctx` maps a group key to an _ExpertCtx, that group's span is
    the *shared* span only and the MoE expert weights are fetched
    router-gated per layer (two-phase step); the scan then also stacks
    per-layer expert activation counts for the residency control plane.

    Returns (x, aux, new_cache, new_xattn, expert) where expert is
    {key: (counts (n_steps, E), reads (n_steps, 2))} (empty without
    expert_ctx)."""

    manifests = manifests or {}
    # page stores stay out of the scan's xs: the body slices layer i's
    # span itself (on the most major axis — a host copy may cut only
    # that one) and moves a host-resident span to device memory
    # explicitly before the layer computes on it
    stores = {k: stacked_p[k] for k in manifests}

    def body(carry, xs):
        x, aux = carry
        p_sl, cache_sl, xattn_sl, layer = xs
        if manifests:
            from repro.core import offload as _offload
            from repro.core import paging as _paging
            p_sl = dict(p_sl)
            for k, store in stores.items():
                span = jax.lax.dynamic_index_in_dim(store, layer, 0,
                                                    keepdims=False)
                p_sl[k] = _paging.unflatten_span(
                    _offload.device_operand(span), manifests[k])
        has_cache = isinstance(cache_sl, dict)
        has_xc = isinstance(xattn_sl, dict)
        new_caches, new_xs, counts = {}, {}, {}
        for i, spec in enumerate(specs):
            key = f"p{i}"
            fetch = (expert_ctx[key].make_fetch(layer)
                     if expert_ctx and key in expert_ctx else None)
            x, nc, nx, a, ec = block_apply(
                cfg, spec, p_sl[key], x, positions=positions,
                cache=cache_sl.get(key) if has_cache else None, mode=mode,
                pos=pos, enc_out=enc_out,
                xattn_cache=xattn_sl if (spec.cross_attn and has_xc) else None,
                policy=policy, causal=causal, expert_fetch=fetch,
                token_groups=token_groups)
            if nc is not None and has_cache:
                new_caches[key] = nc
            if nx is not None:
                new_xs = nx
            if ec is not None:
                counts[key] = ec
            aux = aux + a
        if new_xs:
            out_xattn = new_xs
        elif has_xc:
            out_xattn = xattn_sl
        else:
            out_xattn = jnp.int32(0)
        return (x, aux), (new_caches, out_xattn, counts)

    if policy and policy.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    p_stacked = {f"p{i}": stacked_p[f"p{i}"] for i in range(len(specs))
                 if f"p{i}" not in manifests}
    cache_stacked = cache_group if cache_group else None
    has_x = any(s.cross_attn for s in specs)
    xattn_stacked = xattn_group if has_x else None

    xs = (p_stacked,
          cache_stacked if cache_stacked is not None else
          jnp.zeros((n_steps,), jnp.int32),
          xattn_stacked if xattn_stacked is not None else
          jnp.zeros((n_steps,), jnp.int32),
          jnp.arange(n_steps))
    (x, aux), (new_cache, new_xattn, counts) = jax.lax.scan(
        body, (x, jnp.float32(0.0)), xs,
        unroll=policy.scan_unroll if policy else 1)
    return x, aux, (new_cache if cache_group else None), \
        (new_xattn if has_x else None), counts


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params, tokens, positions,
                 patches=None):
    x = params["embed"]["tokens"][tokens]            # (B,S,E) gather
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.vision_tokens and patches is not None:
        nv = min(cfg.vision_tokens, x.shape[1])
        x = x.at[:, :nv].set(patches[:, :nv].astype(x.dtype))
    if cfg.pos == "learned":                         # sinusoidal stand-in
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x


def encoder_forward(cfg: ModelConfig, params, frames, policy=None):
    """Whisper encoder: frames (B, encS, E) — conv frontend stubbed."""
    B, S, E = frames.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = frames + sinusoidal_positions(positions, E).astype(frames.dtype)
    enc = params["encoder"]
    x, _, _, _, _ = _run_group(
        cfg, (LayerSpec(cross_attn=False),), enc["blocks"], x,
        n_steps=cfg.encoder_layers, positions=positions, cache_group=None,
        mode="encode", pos=None, enc_out=None, xattn_group=None,
        policy=policy, causal=False)
    return apply_norm(cfg, enc["final_norm"], x)


def forward(cfg: ModelConfig, params, tokens, *, cache=None, mode="train",
            frames=None, patches=None, policy: Optional[ExecPolicy] = None,
            paged_blocks=None, fill_len=None, expert_state=None,
            token_groups=None):
    """tokens: (B,S) int32.  mode: train | prefill | decode | chunk_prefill.
    Returns dict(hidden, cache, aux_loss).  Call `unembed` for logits.

    chunk_prefill processes one fixed-width prompt chunk at the row offset
    recorded in cache["pos"]: the chunk's KV is written into the ring at
    absolute positions pos..pos+S-1 and its queries attend to the whole
    ring (history + chunk) under the slot_pos mask.  `fill_len` ((B,) i32)
    gives the true token count of the chunk; padded tail positions are
    clamped to pos+fill_len so they collapse into one causally-masked slot
    instead of wrapping the ring.

    paged_blocks: optional (pages_dict, manifests) from
    core.paging.pack_block_groups — replaces params['blocks'] with paged
    weight spans consumed layer-by-layer inside the scan (the offloaded
    serving path; pages may live in host memory on TPU) — OR a
    core.paging.PagedWeights from pack_block_groups_split for the
    expert-granular path: the scan streams only each layer's *shared*
    span and the MoE experts are fetched router-gated per layer.
    `expert_state` then optionally maps each MoE group key to
    (pool (slots, *span_shape), resident_map (L, E) int32): spans
    whose map entry is >= 0 are read in place from the device pool,
    the rest stream from the host store.  The result dict gains
    "expert_counts" ({key: (n_steps, E)} tokens-routed counts) so the
    host residency cache can learn popularity and account traffic, and
    "expert_reads" ({key: (n_steps, 2)}: per layer, the spans the
    program read from the host store and from the pool, counted in the
    fetch's branches; padding entries of the activated set read none)."""
    B, S = tokens.shape
    if mode == "decode":
        assert cache is not None
        pos = cache["pos"]                           # (B,)
        positions = pos[:, None]
        run_mode = "decode"
    elif mode == "chunk_prefill":
        assert cache is not None
        pos = None
        off = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if fill_len is not None:
            off = jnp.minimum(off, fill_len[:, None])
        positions = cache["pos"][:, None] + off
        run_mode = "chunk"
    else:
        pos = None
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        run_mode = mode if mode == "decode" else ("prefill" if cache is not None
                                                  else "train")
        run_mode = "full"

    enc_out = None
    if cfg.encoder_layers and frames is not None:
        enc_out = encoder_forward(cfg, params, frames, policy)

    x = embed_tokens(cfg, params, tokens, positions, patches)
    aux_total = jnp.float32(0.0)
    new_cache = dict(cache) if cache is not None else None

    if cfg.prologue:
        x, aux, npc, _, _ = _run_group(
            cfg, (cfg.prologue[0],), {"p0": params["prologue"]["p0"]}, x,
            n_steps=len(cfg.prologue), positions=positions,
            cache_group={"p0": cache["prologue"]} if cache is not None else None,
            mode=run_mode if mode != "decode" else "decode",
            pos=pos, enc_out=enc_out, xattn_group=None, policy=policy)
        aux_total += aux
        if new_cache is not None and npc is not None:
            new_cache["prologue"] = npc["p0"]

    cache_group = None
    if cache is not None:
        cache_group = {f"p{i}": cache[f"p{i}"] for i in range(len(cfg.period))}
    xattn_group = cache.get("xattn") if (cache is not None and
                                         cfg.encoder_layers) else None
    if cfg.encoder_layers and cache is None:
        xattn_group = None

    blocks = params["blocks"]
    manifests = None
    expert_ctx = None
    if paged_blocks is not None:
        from repro.core import paging as _paging
        if isinstance(paged_blocks, _paging.PagedWeights):
            blocks, manifests = paged_blocks.pages, paged_blocks.manifests
            if paged_blocks.expert_manifests:
                expert_ctx = {}
                for k, em in paged_blocks.expert_manifests.items():
                    pool, rmap = (expert_state or {}).get(k, (None, None))
                    expert_ctx[k] = _ExpertCtx(paged_blocks.expert_pages[k],
                                               em, pool, rmap)
        else:
            blocks, manifests = paged_blocks
    x, aux, npc, nxc, ecounts = _run_group(
        cfg, cfg.period, blocks, x, n_steps=cfg.num_periods,
        positions=positions, cache_group=cache_group,
        mode=run_mode if run_mode in ("decode", "chunk") else "full",
        pos=pos, enc_out=enc_out, xattn_group=xattn_group, policy=policy,
        manifests=manifests, expert_ctx=expert_ctx,
        token_groups=token_groups)
    aux_total += aux
    if new_cache is not None:
        if npc is not None:
            new_cache.update(npc)
        if nxc is not None:
            new_cache["xattn"] = nxc
        step = jnp.int32(1) if mode == "decode" else jnp.int32(S)
        if mode == "chunk_prefill" and fill_len is not None:
            step = fill_len.astype(jnp.int32)        # per-row true fill
        new_cache["pos"] = cache["pos"] + step

    x = apply_norm(cfg, params.get("final_norm", {}), x)
    out = {"hidden": x, "cache": new_cache, "aux_loss": aux_total}
    if expert_ctx is not None:
        out["expert_counts"] = {k: c for k, (c, _) in ecounts.items()}
        out["expert_reads"] = {k: r for k, (_, r) in ecounts.items()}
    return out


def unembed(cfg: ModelConfig, params, hidden):
    """hidden: (..., E) -> logits (..., V) float32 (with gemma2 softcap)."""
    if cfg.tie_embeddings:
        w = params["embed"]["tokens"]                # (V,E)
        logits = jnp.einsum("...e,ve->...v", hidden.astype(jnp.float32),
                            w.astype(jnp.float32))
    else:
        logits = jnp.einsum("...e,ev->...v", hidden.astype(jnp.float32),
                            params["lm_head"].astype(jnp.float32))
    return softcap(logits, cfg.logit_softcap)
