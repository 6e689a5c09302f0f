import gzip
import json

from conftest import REPO

from bench import trace

SAMPLE = REPO / "bench" / "traces" / "sample.json.gz"


def _synthetic():
    # window [100, 200); ops busy [100,120) [110,130) [150,160) [190,210)
    return {
        "ops": [["fusion.1", 100, 20], ["paged_gqa", 110, 20],
                ["fusion.1", 150, 10], ["copy", 190, 20]],
        "modules": [["jit_decode_chunk(1)", 100, 30],
                    ["jit_prefill_step(2)", 150, 10]],
        "host": [["bench.window", 100, 100], ["bench.step", 100, 60],
                 ["bench.submit", 160, 5], ["bench.step", 165, 35]],
    }


def test_union_busy_and_gaps():
    tr = _synthetic()
    ev = trace.clip(tr["ops"], *trace.window(tr))
    assert trace.union(ev) == [(100, 130), (150, 160), (190, 200)]
    assert trace.busy_ns(ev) == 50
    assert trace.gaps(tr["ops"], 100, 200) == [(130, 150), (160, 190)]


def test_breakdown_names_gaps_by_host_span():
    tr = _synthetic()
    bd = trace.breakdown(tr, 100, 200)
    assert bd["idle_gaps"][0] == ["bench.step", 30e-9]   # midpoint 175
    assert bd["idle_gaps"][1] == ["bench.step", 20e-9]   # midpoint 140
    # paged_gqa [110,130) nests in fusion.1 [100,120)'s tail only partly:
    # the stack treats it as a child, so fusion.1 keeps 30 - 20 = 10 ns
    assert dict(bd["device_ops"])["paged_gqa"] == 20e-9


def test_self_time_leaves_out_nested_ops():
    ev = [["%while.1 = loop", 0, 100], ["%cond.2 = c", 10, 50],
          ["%copy.3 = x", 20, 30], ["%fusion.4 = f", 70, 20]]
    st = dict(trace.self_times(ev))
    assert st == {"%while.1 = loop": 30, "%cond.2 = c": 20,
                  "%copy.3 = x": 30, "%fusion.4 = f": 20}
    assert trace.op_name("%copy.3 = bf16[2] copy(x)") == "%copy.3"


def test_module_and_kernel_totals():
    tr = _synthetic()
    ns, n = trace.total_ns(tr["modules"], lambda s: "decode_chunk" in s)
    assert (ns, n) == (30, 1)
    ns, n = trace.total_ns(tr["ops"], lambda s: "paged_gqa" in s)
    assert (ns, n) == (20, 1)


def test_recorded_trace_reduces():
    # 3 s of a traced Mixtral-8x7B run on one TPU v5 lite, op names cut
    # to their HLO name
    with gzip.open(SAMPLE, "rt") as f:
        tr = json.load(f)
    t0, t1 = trace.window(tr)
    busy = trace.busy_ns(trace.clip(tr["ops"], t0, t1))
    assert 0 < busy <= t1 - t0
    bd = trace.breakdown(tr, t0, t1)
    assert len(bd["device_ops"]) == 10 and bd["idle_gaps"]
    assert sum(g for _, g in bd["idle_gaps"]) <= (t1 - t0 - busy) / 1e9 + 1e-9
