"""Serving launcher: offloading-aware batch inference (the paper's
workload).  Generates HRM policy advice for the requested hardware, then
runs the engine on synthetic requests and reports generation throughput.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --requests 16 --hw l4 [--paged]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--hw", default="l4",
                    help="HRM hardware preset for policy advice")
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--ubatch", type=int, default=4)
    ap.add_argument("--num-ubs", type=int, default=2)
    args = ap.parse_args()

    import jax
    from repro.configs import get_config
    from repro.core import hrm, policy as pol
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.params import init_params
    from repro.serving.engine import Engine, EngineConfig

    enable_compile_cache()
    cfg_full = get_config(args.arch)
    # HRM policy advice is computed for the FULL model on the target hw
    hw = hrm.preset(args.hw)
    wl = pol.Workload(prompt_len=args.prompt_len, gen_len=args.gen_len)
    try:
        advice = pol.search(cfg_full, hw, wl)["best"]
        print("[serve] HRM policy advice for", args.hw, ":",
              advice["policy"], f"est {advice['throughput']:.1f} tok/s")
    except RuntimeError as e:
        print("[serve] HRM policy:", e)

    cfg = cfg_full.smoke() if args.smoke else cfg_full
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, EngineConfig(
        ubatch=args.ubatch, num_ubs=args.num_ubs,
        max_seq=args.prompt_len + args.gen_len + 8, paged=args.paged))
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        n = int(rng.integers(4, args.prompt_len + 1))
        eng.submit(rng.integers(2, cfg.vocab_size, n), args.gen_len)
    t0 = time.time()
    out = eng.run_until_idle()
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(json.dumps({"requests": len(out), "tokens": total,
                      "seconds": round(dt, 2),
                      "tok_per_s": round(total / dt, 2),
                      "paged": args.paged}))


if __name__ == "__main__":
    main()
