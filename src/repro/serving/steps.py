"""Serving step functions: prefill, decode (serve_step), and the masked
multi-token ``decode_chunk`` used by the continuous-batching engine.

These are the functions the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` shapes, and the engine jits for real serving.

Expert-granular paging (core.paging.PagedWeights with expert manifests)
changes the step signatures: each step takes a trailing ``expert_state``
pytree ({key: (pool, resident_map)} — the device residency snapshot) and
returns per-layer expert activation counts so the engine's host-side
residency cache can learn popularity and account H2D traffic.
``_expert_granular`` is the single switch deciding which shape a factory
produces.  With paged weights of either granularity the factory holds
only the manifests: the page stores arrive in ``params["blocks"]`` on
every call (``paging.page_stores``).

Block-granular paged KV changes no signatures at all: the cache pytree
the engine composes per dispatch carries the shared block arena plus a
``page_table`` leaf per paged period position, and
``models.attention`` dispatches decode writes/gathers on its presence
(``kvcache.is_paged``).  ``decode_chunk`` therefore runs unchanged over
dense and paged pools — the masked-row semantics (frozen ``pos``,
garbage scatter at the frozen slot) land in the trash block when a row
maps no blocks there.  Prefill (monolithic fill AND staged chunks)
always runs on a dense scratch; the paged pool is only ever written by
the slot-insert ops, with blocks booked host-side by core.blockpool.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import paging
from repro.models.model import ExecPolicy, forward, unembed
from repro.serving.sampling import sample


def _expert_granular(paged_blocks) -> bool:
    return (isinstance(paged_blocks, paging.PagedWeights)
            and bool(paged_blocks.expert_manifests))


def _bound(paged_blocks, params):
    """The step's paged layout: the factory's manifests over the page
    stores the call passed in ``params["blocks"]`` (``paging.page_stores``
    — arguments, so they keep their pinned_host placement)."""
    if paged_blocks is None:
        return None
    return paging.bind_page_stores(paged_blocks, params["blocks"])


def make_prefill_step(cfg: ModelConfig,
                      policy: Optional[ExecPolicy] = None) -> Callable:
    """(params, tokens, extras...) -> last-position logits.
    The prefill_* dry-run shapes lower this without a cache (pure
    prompt-processing throughput); the engine variant below fills one."""

    def prefill_step(params, tokens, **extras):
        out = forward(cfg, params, tokens, mode="train", policy=policy,
                      **extras)
        logits = unembed(cfg, params, out["hidden"][:, -1])
        return logits

    return prefill_step


def make_prefill_fill_step(cfg: ModelConfig,
                           policy: Optional[ExecPolicy] = None,
                           *, paged_blocks=None) -> Callable:
    """Engine path: also writes the KV cache.  `lens` (B,) are the true
    per-row prompt lengths: logits are taken at each row's own final
    position (hidden[:, -1] would read the zero-padded tail for any row
    shorter than the bucket width) and the cache's pos is set per row."""

    expert = _expert_granular(paged_blocks)

    def prefill_step(params, tokens, cache, lens, expert_state=None):
        out = forward(cfg, params, tokens, cache=cache, mode="prefill",
                      policy=policy,
                      paged_blocks=_bound(paged_blocks, params),
                      expert_state=expert_state)
        cache = out["cache"]
        cache["pos"] = lens.astype(jnp.int32)
        idx = jnp.maximum(lens - 1, 0)
        hidden = jnp.take_along_axis(
            out["hidden"], idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = unembed(cfg, params, hidden)
        if expert:
            return logits, cache, out["expert_counts"]
        return logits, cache

    return prefill_step


def make_prefill_chunk(cfg: ModelConfig, policy: Optional[ExecPolicy] = None,
                       *, paged_blocks=None) -> Callable:
    """Chunked-prefill admission step (the CGOPipe overlap path): process
    ONE fixed-width chunk of a prompt at the offset recorded in
    cache["pos"], writing its KV into the ring incrementally and carrying
    hidden state to the final-position logits.

    (params, tokens (B,C), cache, fill_len (B,) i32) -> (logits, cache)

    `fill_len` is the number of true tokens in this chunk (< C only for
    the final chunk); the returned logits are taken at the chunk's last
    true position, so the call covering the end of the prompt yields
    exactly the logits a monolithic prefill would produce there.  The
    returned cache's pos advances by fill_len — feeding chunks back in
    sequence drains a prompt of any length through one compiled shape per
    chunk-width bucket."""

    expert = _expert_granular(paged_blocks)

    def prefill_chunk(params, tokens, cache, fill_len, expert_state=None):
        out = forward(cfg, params, tokens, cache=cache, mode="chunk_prefill",
                      policy=policy,
                      paged_blocks=_bound(paged_blocks, params),
                      fill_len=fill_len, expert_state=expert_state)
        idx = jnp.maximum(fill_len - 1, 0)
        hidden = jnp.take_along_axis(
            out["hidden"], idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = unembed(cfg, params, hidden)
        if expert:
            return logits, out["cache"], out["expert_counts"]
        return logits, out["cache"]

    return prefill_chunk


def make_serve_step(cfg: ModelConfig,
                    policy: Optional[ExecPolicy] = None) -> Callable:
    """One decode step: (params, cache, tokens (B,1)) ->
    (next_token (B,), logits (B,V), new_cache).  Greedy head; the engine
    applies temperature sampling on the returned logits instead when
    configured."""

    def serve_step(params, cache, tokens):
        out = forward(cfg, params, tokens, cache=cache, mode="decode",
                      policy=policy)
        logits = unembed(cfg, params, out["hidden"][:, -1])
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, out["cache"]

    return serve_step


def make_decode_chunk(cfg: ModelConfig, policy: Optional[ExecPolicy] = None,
                      *, paged_blocks=None, temperature: float = 0.0,
                      eos_id: int = 1, chunk: int = 8,
                      token_groups: Optional[int] = None) -> Callable:
    """Masked multi-token decode for the slot-pool engine: `chunk` decode
    steps under one ``lax.scan`` so Python/dispatch overhead is amortized
    between admission checks, with a per-row *active* mask so drained /
    free slots are carried along at fixed shape without emitting tokens or
    advancing their cache position.

    (params, cache, tok (B,1), active (B,) bool, rem (B,) i32, key) ->
    (cache, tok, active, rem, toks (chunk,B) i32, emitted (chunk,B) bool)

    Per step, an active row samples a token, decrements its remaining
    quota, and goes inactive on EOS or quota exhaustion; the emitted mask
    marks exactly the (step, row) pairs whose token belongs to a request.
    Inactive rows keep their `pos` (restored after the forward), which is
    what isolates them from active neighbors; the fixed-shape forward
    still scatters a KV write at their frozen `pos % W` slot each step,
    so a drained row's cache content is garbage until `reset_slot` +
    refill — it must never be read without that reset.

    Expert-granular paging adds a trailing ``expert_state`` arg (the
    residency snapshot, constant across the chunk) and a trailing
    ``counts`` output ({key: (chunk, n_steps, E)} — per inner step, so
    the host accounting books each step's distinct activations against
    the snapshot it actually read).

    token_groups=G (module-based batching): B is G·ubatch — the engine
    concatenates G rotation groups' slot caches and the MoE FFN stages
    all G groups' routed tokens against one expert-span read per layer
    step.  counts then gains a group axis: {key: (chunk, n_steps, G, E)}.
    """

    expert = _expert_granular(paged_blocks)

    def decode_chunk(params, cache, tok, active, rem, key,
                     expert_state=None):
        paged = _bound(paged_blocks, params)

        def body(carry, _):
            cache, tok, active, rem, key = carry
            pos0 = cache["pos"]
            out = forward(cfg, params, tok, cache=cache, mode="decode",
                          policy=policy, paged_blocks=paged,
                          expert_state=expert_state,
                          token_groups=token_groups)
            logits = unembed(cfg, params, out["hidden"][:, -1])
            key, sub = jax.random.split(key)
            nxt = sample(logits, sub, temperature=temperature)
            new_cache = out["cache"]
            new_cache["pos"] = jnp.where(active, new_cache["pos"], pos0)
            emitted = active
            rem2 = rem - emitted.astype(jnp.int32)
            active2 = active & (nxt != eos_id) & (rem2 > 0)
            tok2 = jnp.where(active, nxt, tok[:, 0])[:, None]
            ys = (nxt, emitted) + ((out["expert_counts"],) if expert else ())
            return (new_cache, tok2, active2, rem2, key), ys

        (cache, tok, active, rem, key), ys = jax.lax.scan(
            body, (cache, tok, active, rem, key), None, length=chunk)
        if expert:
            toks, emitted, counts = ys
            return cache, tok, active, rem, toks, emitted, counts
        toks, emitted = ys
        return cache, tok, active, rem, toks, emitted

    return decode_chunk
