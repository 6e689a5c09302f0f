"""Benchmark of the offloaded serving engine on one chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json``: draws weights and traffic from the
seed, warms up, measures ``--seconds`` of closed-loop offline-batch
generation, and checks what the window served against the plain float32
reference.  The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers close
stderr.  It exits non-zero and prints no result where no TPU with the
cell's chips is found, or where the program or a file of the cell is
missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the raw and reduced trace to this "
                         "directory (for reading one by hand)")
    return ap.parse_args(argv)


def enable_cache(root: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def checks(cell, out) -> dict:
    """The numbers compared with the reference, each with its limit."""
    seqs = out["seqs"]
    limits = cell.settings["check"]["limits"]
    if not seqs:
        return {"served_tokens": {"value": 0,
                                  "limit": cell.settings["check"]
                                  ["sample_tokens"]}}
    gaps = cell.reference.gaps(out["dims"], out["seed"], seqs)
    flat = [float(g) for row in gaps for g in row]
    # the widest gap is read but not compared: one near-tied router
    # choice sets it, and the control's widest gap overlaps the program's
    print(f"reading max_gap (not compared): {max(flat)!r}", file=sys.stderr)
    return {"mean_gap": {"value": sum(flat) / len(flat),
                         "limit": limits["mean_gap"]},
            "served_tokens": {"value": len(flat), "limit": 1}}


def passed(c: dict) -> bool:
    ok = True
    for name, v in c.items():
        if name == "served_tokens":
            ok &= v["value"] >= v["limit"]
        else:
            ok &= math.isfinite(v["value"]) and v["value"] <= v["limit"]
    return bool(ok)


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True,
         impl=None, mutate=None, t_start: float = None) -> int:
    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    try:
        cell = spec.load_cell(args.workload, root)
    except (spec.SpecError, KeyError, json.JSONDecodeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {root}",
              file=sys.stderr)
        return 2
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 3
    if devs[0].platform != "cpu":
        print(f"compile cache: {enable_cache(root)}", file=sys.stderr)

    from bench import serve

    out = serve.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         t_start, impl=impl, mutate=mutate,
                         trace_dir=args.keep_trace)
    out["seed"] = args.seed
    rec = out["record"]
    dev = out["device"]
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and rec.trace is not None and rec.trace_window:
        from bench import trace as trace_mod
        t0, t1 = rec.trace_window
        device["busy_s"] = trace_mod.busy_ns(
            trace_mod.clip(rec.trace["ops"], t0, t1)) / 1e9
        device["window_s"] = (t1 - t0) / 1e9
        result["breakdown"] = trace_mod.breakdown(rec.trace, t0, t1)
    from bench import window
    tp = window.tpot_s(rec.ticks)
    print(f"requests that held a slot in the window: {len(tp)}; "
          f"generated tokens {window.generated_tokens(rec.ticks)} in "
          f"{window.window_seconds(rec.ticks):.3f} s", file=sys.stderr)
    t0 = time.perf_counter()
    c = checks(cell, out)
    print(f"reference check: {time.perf_counter() - t0:.2f} s over "
          f"{len(out['seqs'])} requests", file=sys.stderr)
    result["correct"] = passed(c)
    result["checks"] = c
    for name, v in c.items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
