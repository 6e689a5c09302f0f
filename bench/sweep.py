"""Slot-pool sizing sweep of a cell, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --pools 8x2,16x2,32x2 [--kv-ratios 0.25]

Runs the cell once per slot pool (``ubatch`` x ``num_ubs``) and KV ratio
with every other setting as the cell file has it, and prints one JSON
line per run: generated tokens per second, p95 time per output token,
mean tick, KV spills in the window and device memory.  Used once to
choose the values frozen in a cell file; the benchmark never runs it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, serve, spec, window  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pools", required=True)
    ap.add_argument("--kv-ratios", default=None)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache(ROOT)
    base = spec.load_cell(args.workload)
    kvs = ([float(x) for x in args.kv_ratios.split(",")]
           if args.kv_ratios else [base.settings["engine"]["kv_gpu_ratio"]])
    for pool in args.pools.split(","):
        ub, nu = (int(x) for x in pool.split("x"))
        for kv in kvs:
            cell = dataclasses.replace(
                base, settings=copy.deepcopy(base.settings))
            cell.settings["engine"].update(ubatch=ub, num_ubs=nu,
                                           kv_gpu_ratio=kv)
            t0 = time.perf_counter()
            out = serve.run_cell(cell, args.seed, args.seconds, False, t0)
            ticks = out["record"].ticks
            kv0, kv1 = out["record"].kv
            stats = out["device"].memory_stats() or {}
            print(json.dumps({
                "ubatch": ub, "num_ubs": nu, "kv_gpu_ratio": kv,
                "gen_tokens_per_s": window.gen_tokens_per_s(ticks),
                "tpot_p95_ms": 1000 * window.p95(
                    window.tpot_s(ticks).values()),
                "ticks": len(ticks),
                "tick_mean_s": window.window_seconds(ticks) / len(ticks),
                "setup_s": out["record"].setup_s,
                "kv_spills": kv1.get("spills", 0) - kv0.get("spills", 0),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}),
                flush=True)
            del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
