"""Smoke run of the Mixtral-8x7B serving engine on one TPU chip.

    python chip_smoke.py [--seed N]

Builds Mixtral-8x7B at its published widths with depth cut to 2 layers
(random weights from ``--seed``) and serves the same 16 seeded requests
through ``repro.serving.engine.Engine`` twice, in one process:

  resident   the default EngineConfig path: continuous batching, dense KV
             rings, every weight on the device;
  offloaded  the paper's regime: expert-granular paged weights
             (w_gpu_ratio 0.25) and block-paged KV (kv_gpu_ratio 0.5), the
             host tiers in pinned host memory, and the Pallas paged-decode
             kernel in the decode step.

Each phase checks that every request finishes with its quota (or EOS),
that every token is in the vocabulary and that the prefill logits it
samples from are finite.  The offloaded phase also checks that its host
stores are pinned_host, that its compiled decode step holds the kernel
(``tpu_custom_call``), that weight spans and KV blocks really moved, and
that its final-position prefill logits match the resident phase within
the bf16 tolerance below.  The times, rates and memory it prints describe
this smoke run only; they are not benchmark numbers.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
A failed check, or any platform other than TPU, exits non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

LAYERS = 2
UBATCH, NUM_UBS, MAX_SEQ = 8, 2, 256
N_REQUESTS, NEW_TOKENS = 16, 32
# prompt lengths are drawn within 16 tokens below each bucket (64–192
# tokens in all), so each phase compiles three prefill widths, not nine
PROMPT_BUCKETS = (80, 128, 192)
# Offloaded vs resident final-position prefill logits: the same weights
# through different programs (the activated-expert subset vs all experts,
# fused differently), so each bf16 rounding (relative step 2^-8) may land
# differently; a few such steps through 2 layers stay within 2^-5 of the
# largest logit.
LOGIT_RTOL = 2 ** -5


class CheckFailed(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _phase(name, cfg, params, prompts, ecfg, policy=None, *,
           after_init=None):
    """Serve ``prompts`` on a fresh Engine; returns (engine, transcripts,
    prefill logits in admission order, compiled decode-step text)."""
    import jax
    import numpy as np
    from repro.serving.engine import Engine

    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    eng = Engine(cfg, params, ecfg, policy=policy)
    if after_init is not None:
        after_init()
    t_init = time.perf_counter() - t0

    logits = []
    sample_first = eng._sample_first

    def recording_sample(lg):
        logits.append(np.asarray(lg, np.float32)[0])
        return sample_first(lg)

    decode = eng._decode_chunk
    hlo = []

    def recording_decode(*args):
        if not hlo:
            hlo.append(decode.lower(*args).compile().as_text())
        return decode(*args)

    eng._sample_first = recording_sample
    eng._decode_chunk = recording_decode
    for p in prompts:
        eng.submit(p, NEW_TOKENS)
    t0 = time.perf_counter()
    out = eng.run_until_idle()
    wall = time.perf_counter() - t0
    jax.monitoring.unregister_event_duration_listener(on_duration)

    check(len(out) == len(prompts), f"{name}: {len(out)} of {len(prompts)} "
          "requests finished")
    for rid, toks in out.items():
        check(len(toks) == NEW_TOKENS
              or (0 < len(toks) < NEW_TOKENS and toks[-1] == ecfg.eos_id),
              f"{name}: request {rid} stopped at {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"{name}: request {rid} emitted a token outside the vocabulary")
    check(len(logits) >= len(prompts), f"{name}: {len(logits)} prefills")
    check(all(np.isfinite(lg).all() for lg in logits),
          f"{name}: non-finite prefill logits")
    check(bool(hlo), f"{name}: no decode step ran")
    tokens = sum(len(t) for t in out.values())
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] engine init {t_init:.2f} s; serve {wall:.2f} s for "
          f"{tokens} tokens (smoke-run rate {tokens / wall:.1f} tok/s, "
          f"compiles included); backend compile {compile_s[0]:.1f} s; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    return eng, out, logits, hlo[0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import numpy as np

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke.py needs a TPU; found "
              f"{dev.platform!r}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import ExecPolicy
    from repro.models.params import init_params
    from repro.serving.engine import EngineConfig

    print("compile cache:", enable_compile_cache(), flush=True)
    full = get_config("mixtral-8x7b")
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    print(f"model: {full.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, {cfg.num_experts} experts "
          f"top-{cfg.top_k}, vocab {cfg.vocab_size}, {cfg.dtype}); depth "
          f"cut {full.num_layers} -> {LAYERS} layers: 32 layers are ~93 GB "
          "in bf16 and the engine initialises its weights on the device, "
          "so depth is cut to what one 16 GB chip holds (2 layers: ~6.3 GB "
          "of weights)", flush=True)
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.key(args.seed))
    jax.block_until_ready(params)
    print(f"params: seed {args.seed}, initialised in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(2, cfg.vocab_size,
                            int(rng.choice(PROMPT_BUCKETS))
                            - int(rng.integers(0, 16))).astype(np.int32)
               for _ in range(N_REQUESTS)]
    print(f"requests: {N_REQUESTS}, prompt lengths "
          f"{sorted(len(p) for p in prompts)}, {NEW_TOKENS} new tokens each",
          flush=True)

    ecfg = EngineConfig(ubatch=UBATCH, num_ubs=NUM_UBS, max_seq=MAX_SEQ)
    eng, res_out, res_logits, _ = _phase("resident", cfg, params, prompts,
                                         ecfg)
    del eng
    gc.collect()

    def free_unpacked_blocks():
        # the offloaded engine serves from its packed stores; dropping
        # the device copy of the unpacked block weights makes room
        params.pop("blocks")

    off_cfg = dataclasses.replace(
        ecfg, expert_paged=True, w_gpu_ratio=0.25,
        kv_paged=True, kv_gpu_ratio=0.5, block_tokens=16)
    eng, off_out, off_logits, hlo = _phase(
        "offloaded", cfg, params, prompts, off_cfg,
        ExecPolicy(paged_attn_impl="pallas"), after_init=free_unpacked_blocks)

    pw = eng.paged_blocks
    weight_kinds = {a.sharding.memory_kind for a in
                    [*pw.pages.values(), *pw.expert_pages.values()]}
    kv_kinds = {a.sharding.memory_kind for g in eng._kv_host.values()
                for a in g.values()}
    print(f"[offloaded] weight store memory kinds {sorted(weight_kinds)}, "
          f"KV host tier {sorted(kv_kinds)}", flush=True)
    check(weight_kinds == {"pinned_host"}, "weight stores not in pinned_host")
    check(eng._kv_pinned and kv_kinds == {"pinned_host"},
          "KV host tier not in pinned_host")
    check("tpu_custom_call" in hlo,
          "the compiled decode step holds no Pallas kernel")
    wt, kt = eng.weight_traffic(), eng.kv_traffic()
    print(f"[offloaded] weight H2D bytes {wt['h2d_bytes']} (expert spans "
          f"{wt['expert_bytes']}: {wt['misses']} misses, {wt['prefetches']} "
          f"prefetches, {wt['hits']} hits); KV spills {kt['spills']}, "
          f"fetches {kt['misses'] + kt['prefetches']} (d2h "
          f"{kt['d2h_bytes']} B, h2d {kt['h2d_bytes']} B)", flush=True)
    check(wt["h2d_bytes"] > 0 and wt["expert_bytes"] > 0,
          "no expert span moved host -> device")
    check(kt["spills"] > 0 and kt["misses"] + kt["prefetches"] > 0,
          "no KV block was spilled and fetched back")

    n = min(len(res_logits), len(off_logits))
    check(n == N_REQUESTS, f"{n} comparable prefills")
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(res_logits[:n], off_logits[:n]))
    scale = max(float(np.abs(a).max()) for a in res_logits[:n])
    tol = LOGIT_RTOL * scale
    print(f"prefill logits, offloaded vs resident: max |diff| {diff:.6g} "
          f"(tolerance {tol:.6g} = 2^-5 x max |logit| {scale:.6g})",
          flush=True)
    check(diff <= tol, "offloaded prefill logits diverge from resident")
    same = sum(res_out[r] == off_out.get(r) for r in res_out)
    pos = sum(len(t) for t in res_out.values())
    agree = sum(a == b for r, t in res_out.items()
                for a, b in zip(t, off_out.get(r, [])))
    print(f"greedy agreement (random weights: near-tied logits, printed "
          f"only): {same}/{len(res_out)} transcripts identical, "
          f"{agree}/{pos} tokens equal by position", flush=True)

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
