"""Finds a cell's parts by name.

``BENCHMARK.json`` at the root names the cells (``workloads``), the
configurations and the metrics.  Everything that belongs to one of them
sits in a file of its own under ``bench/``, found by name alone:

  bench/configs/<config>.json   model configuration as run
  bench/references/<ref>.py     what the harness knows of an architecture
                                (the configuration's ``reference``)
  bench/traffic/<traffic>.json  traffic mix, read by bench/traffic.py
  bench/cells/<workload>.json   engine settings and correctness limits
  bench/metrics/<metric>.py     reader of one metric: ``read(run)``

A reference module is the harness's only knowledge of an architecture:
the sizes it needs, its seeded weights and the engine's tree, the work a
token costs, its decode kernel's name, and the plain reference that
decides ``correct`` (``REFERENCE_EXPORTS``).  Adding a cell,
configuration, architecture, traffic mix or metric therefore means new
files and new entries in ``BENCHMARK.json``, and no edit elsewhere.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# what every bench/references/<ref>.py defines:
#   dims(cfg) -> dict              the sizes the rest needs
#   layer(dims, seed, index)       seeded weights of one layer, on device
#   top(dims, seed)                embedding, final norm, output head
#   engine_params(cfg, dims, seed) the engine's parameter tree
#   prefill_flops(dims, n)         model FLOPs of an n-token prompt
#   decode_flops(dims, ctx)        model FLOPs of a token that sees ctx
#   decode_kernel_work(dims, ctx)  (flops, bytes) of the decode kernel,
#                                  all layers, for that token
#   DECODE_KERNEL                  a fragment of that kernel's trace name
#   gaps(dims, seed, seqs, control=False)   the correctness readings
REFERENCE_EXPORTS = ("dims", "layer", "top", "engine_params",
                     "prefill_flops", "decode_flops", "decode_kernel_work",
                     "DECODE_KERNEL", "gaps")


class SpecError(RuntimeError):
    pass


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str]        # None for end-to-end metrics
    workloads: Optional[List[str]]
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                # bench/configs/<config>.json
    reference: ModuleType       # bench/references/<config's reference>.py
    traffic: dict               # bench/traffic/<traffic>.json
    settings: dict              # bench/cells/<name>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    if not hasattr(mod, "read"):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def _metrics(entries, cell_name: str, bench_dir: Path,
             per_layer: bool) -> List[Metric]:
    out = []
    for m in entries:
        wl = m.get("workloads")
        if wl is not None and cell_name not in wl:
            continue
        out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                          m.get("layer") if per_layer else None, wl,
                          load_metric_reader(m["name"], bench_dir)))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    bench_dir = root / "bench"
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    if "reference" not in config:
        raise SpecError(f"config {w['config']!r} names no reference")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        reference=load_reference(config["reference"], bench_dir),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        settings=_load_json(bench_dir / "cells" / f"{name}.json"),
        end_to_end=_metrics(bench["end_to_end"], name, bench_dir, False),
        per_layer=_metrics(bench["per_layer"], name, bench_dir, True))


def model_config(config: dict):
    """The engine's ModelConfig for a configuration file: the repo's
    config of ``run.arch`` with ``run.overrides`` applied, checked field
    by field against the published keys named in ``run.matches`` (a
    drift of the program's config fails here, not silently)."""
    import dataclasses

    from repro.configs import get_config

    run = config["run"]
    cfg = dataclasses.replace(get_config(run["arch"]), **run["overrides"])
    for field, key in run.get("matches", {}).items():
        have, want = getattr(cfg, field), config[key]
        if have != want:
            raise SpecError(f"{run['arch']}: {field} is {have}, the "
                            f"configuration file says {key}={want}")
    return cfg


def load_peaks(bench_dir: Path = BENCH_DIR) -> dict:
    """Peak rates by ``device_kind`` (bench/peaks.json, with its source)."""
    return _load_json(bench_dir / "peaks.json")


def load_reference(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reference module a configuration file names, with every one of
    ``REFERENCE_EXPORTS``."""
    path = bench_dir / "references" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reference {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    missing = [e for e in REFERENCE_EXPORTS if not hasattr(mod, e)]
    if missing:
        raise SpecError(f"reference {path} does not define "
                        f"{', '.join(missing)}")
    return mod
