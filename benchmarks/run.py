"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only e2e|policy|kernels|hrm|tp|engine]
"""
from __future__ import annotations

import argparse
import sys
import time


def _section(name):
    print(f"# ---- {name} " + "-" * max(1, 60 - len(name)), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    def want(k):
        return args.only is None or args.only == k

    t0 = time.time()
    if want("e2e"):
        _section("Fig.7 / Tab.4: end-to-end throughput by schedule")
        from benchmarks import bench_e2e
        bench_e2e.run()
    if want("policy"):
        _section("Tab.5: policy ablation")
        from benchmarks import bench_policy
        bench_policy.run()
    if want("kernels"):
        _section("Fig.9: KV-transfer vs attention vs MoE FFN")
        from benchmarks import bench_kernels
        bench_kernels.run()
    if want("hrm"):
        _section("Fig.4/5: HRM turning points; Fig.10: policy-vs-hardware")
        from benchmarks import bench_hrm
        bench_hrm.run()
    if want("tp"):
        _section("Fig.8: tensor-parallel scaling")
        from benchmarks import bench_tp_scaling
        bench_tp_scaling.run()
    if want("engine"):
        _section("engine micro-benchmark (real decode steps, CPU smoke)")
        from benchmarks import bench_engine
        bench_engine.run()
    print(f"# benchmarks done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
