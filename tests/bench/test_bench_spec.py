import json
import re
import shutil

import pytest

from conftest import REPO, make_root

from bench import spec

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_resolves_from_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer and len(cell.end_to_end) >= 2


def test_configs_match_the_engine():
    for c in BENCH["configs"]:
        cfg = spec.model_config(json.loads((REPO / c["file"]).read_text()))
        assert cfg.num_layers == json.loads(
            (REPO / c["file"]).read_text())["num_hidden_layers"]


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = make_root(tmp_path)
    # a new metric reader, traffic mix and cell: new files and new entries
    metrics = root / "bench" / "metrics"
    metrics.unlink()
    shutil.copytree(REPO / "bench" / "metrics", metrics)
    (metrics / "queue_depth.py").write_text(
        "def read(run):\n    return 7.0\n")
    (root / "bench" / "traffic" / "burst.json").write_text(
        (root / "bench" / "traffic" / "tiny.json").read_text())
    (root / "bench" / "cells" / "tiny-moe.offload.burst.json").write_text(
        (root / "bench" / "cells" / "tiny-moe.offload.tiny.json")
        .read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-moe.offload.burst",
                               "config": "tiny-moe", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queue_depth", "unit": "requests",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine and scheduler",
                               "moves": "gen_tokens_per_s",
                               "workloads": ["tiny-moe.offload.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny-moe.offload.burst", root)
    assert cell.traffic["name"] == "tiny"
    reader = {m.name: m for m in cell.per_layer}["queue_depth"]
    assert reader.read(None) == 7.0
    old = spec.load_cell("tiny-moe.offload.tiny", root)
    assert "queue_depth" not in {m.name for m in old.per_layer}


def test_unknown_cell_and_missing_reader(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    missing = dict(bench["per_layer"][0], name="missing")
    missing.pop("workloads", None)
    bench["per_layer"].append(missing)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell("tiny-moe.offload.tiny", root)
