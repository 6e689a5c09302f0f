"""Readings that set a cell's correctness limits: the program's and the
control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 101,102,... \
        --seconds <s>

For each seed it runs the cell as bench/run.py does (set-up, a window of
``--seconds`` at the cell's own load), samples the finished requests as
the run does, and reads two sets of numbers on the same prompts and
served tokens: the program's (gaps of the served tokens under the
float32 reference) and the control's (gaps of the tokens the float8
reference puts first).  One JSON line per seed; a limit goes between the
largest program reading and the smallest control reading.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, serve, spec  # noqa: E402


def readings(gaps) -> dict:
    flat = [float(g) for row in gaps for g in row]
    return {"max_gap": max(flat), "mean_gap": sum(flat) / len(flat),
            "served_tokens": len(flat)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache(ROOT)
    cell = spec.load_cell(args.workload)
    ref = cell.reference
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = serve.run_cell(cell, seed, args.seconds, False, t0)
        t1 = time.perf_counter()
        prog = readings(ref.gaps(out["dims"], seed, out["seqs"]))
        t2 = time.perf_counter()
        ctrl = readings(ref.gaps(out["dims"], seed, out["seqs"],
                                 control=True))
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "requests": len(out["seqs"]),
                          "run_s": t1 - t0, "reference_s": t2 - t1}),
              flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
