"""An architecture the harness has never drawn enters as new files only:
a reference module, a configuration and a cell written into a checkout
made by ``make_root``, with every file under bench/ as it is.  The
stand-in is the program's DeepSeek-V3 layout at smoke widths, whose
engine tree has MLA leaves, dense prologue layers and a shared expert."""
import json
import shutil
import time
from pathlib import Path

import pytest

from conftest import TINY_CELL, make_root

from bench import flops, run, serve, spec, window

FIXTURE = Path(__file__).with_name("mla_fixture_reference.py")
SEED = 2 ** 32 + 977
ARGS = ["--workload", "tiny-mla.offload.tiny", "--seed", str(SEED),
        "--seconds", "1.5", "--trace", "0"]
CONFIG = {
    "name": "tiny-mla", "source": "test stand-in at smoke widths",
    "reference": "mla_fixture",
    "run": {"arch": "deepseek-v3-671b",
            "overrides": {"num_layers": 5, "d_model": 64, "d_ff": 128,
                          "dense_d_ff": 128, "vocab_size": 512,
                          "head_dim": 24, "num_heads": 4, "num_kv_heads": 2,
                          "num_experts": 8, "top_k": 2, "q_lora_rank": 32,
                          "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                          "qk_rope_head_dim": 8, "v_head_dim": 16}},
}


def _mla_root(tmp_path) -> Path:
    root = make_root(tmp_path)
    bench_dir = root / "bench"
    shutil.copy(FIXTURE, bench_dir / "references" / "mla_fixture.py")
    (bench_dir / "configs" / "tiny-mla.json").write_text(json.dumps(CONFIG))
    (bench_dir / "cells" / "tiny-mla.offload.tiny.json").write_text(
        json.dumps(TINY_CELL))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mla", "source": "test",
                             "file": "bench/configs/tiny-mla.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-mla.offload.tiny",
                               "config": "tiny-mla", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_new_architecture_needs_only_new_files(tmp_path, capsys,
                                                  monkeypatch):
    import repro.serving.engine as engine_mod

    root = _mla_root(tmp_path)
    got = {}

    class RecordingEngine(engine_mod.Engine):
        def __init__(self, cfg, params, *a, **kw):
            got["params"] = params
            super().__init__(cfg, params, *a, **kw)

    run_cell, reset_work = serve.run_cell, serve.Loop.reset_work

    def recording_run_cell(cell, *a, **kw):
        got["cell"] = cell
        got["out"] = run_cell(cell, *a, **kw)
        return got["out"]

    def window_reset_work(self):     # the counts of the window start here
        log = got["cell"].reference.LOG
        log["model_flops"].clear()
        log["kernel_work"].clear()
        reset_work(self)

    monkeypatch.setattr(engine_mod, "Engine", RecordingEngine)
    monkeypatch.setattr(serve, "run_cell", recording_run_cell)
    monkeypatch.setattr(serve.Loop, "reset_work", window_reset_work)

    rc = run.main(ARGS, root=root, require_chip=False, impl="interpret",
                  t_start=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}

    ref = got["cell"].reference
    assert Path(ref.__file__) == root / "bench" / "references" / \
        "mla_fixture.py"
    # the engine ran on the fixture's tree, with groups moe_gqa never draws
    params = got["params"]
    assert params is ref.LOG["engine_params"][-1]
    assert "ffn" in params["prologue"]["p0"]
    assert "wdkv" in params["blocks"]["p0"]["attn"]
    assert "shared" in params["blocks"]["p0"]["moe"]
    # the check handed the fixture its own sizes and the run's seed
    [(dims, seed)] = ref.LOG["gaps"]
    assert dims is ref.LOG["dims"][-1] and seed == SEED

    # the window's work is the fixture's counts, and mfu reads them
    rec = got["out"]["record"]
    assert rec.model_flops == sum(ref.LOG["model_flops"]) > 0
    assert rec.kernel_flops == sum(f for f, _ in ref.LOG["kernel_work"]) > 0
    assert rec.kernel_bytes == sum(b for _, b in ref.LOG["kernel_work"])
    peak = spec.load_peaks()["TPU v5 lite"]
    rec.peak = peak
    read = lambda name: spec.load_metric_reader(  # noqa: E731
        name, root / "bench")(rec)
    assert read("mfu") == pytest.approx(
        100 * rec.model_flops / window.window_seconds(rec.ticks)
        / peak["bf16_flops_per_s"])

    # the roofline reader times the fixture's kernel and no other
    rec.trace = {"ops": [["paged_mla_decode.3", 100, 40],
                         ["paged_gqa_decode_fused.1", 150, 30],
                         ["fusion.2", 200, 10]],
                 "modules": [], "host": [["bench.window", 100, 200]]}
    rec.trace_window = (100, 300)
    assert read("paged_decode_roofline") == pytest.approx(
        100 * flops.roofline_seconds(rec.kernel_flops, rec.kernel_bytes,
                                     peak) / 40e-9)


@pytest.mark.parametrize("export", spec.REFERENCE_EXPORTS)
def test_a_reference_without_an_export_is_refused(tmp_path, capsys, export):
    root = _mla_root(tmp_path)
    path = root / "bench" / "references" / "mla_fixture.py"
    path.write_text(path.read_text() + f"\ndel {export}\n")
    with pytest.raises(spec.SpecError,
                       match=f"mla_fixture.py does not define {export}$"):
        spec.load_cell("tiny-mla.offload.tiny", root)
    rc = run.main(ARGS, root=root, require_chip=False)
    assert rc != 0 and capsys.readouterr().out == ""
