"""End-to-end runs of the harness at a tiny size on the CPU: the look for
a chip is skipped and the Pallas kernel runs in the interpreter."""
import json
import time

import numpy as np
import pytest

from conftest import TINY_CELL, make_root

from bench import run, serve, spec

ARGS = ["--workload", "tiny-moe.offload.tiny", "--seed", str(2 ** 33 + 7),
        "--seconds", "1.5", "--trace", "0"]


def _run(root, capsys, **kw):
    rc = run.main(ARGS, root=root, require_chip=False, impl="interpret",
                  t_start=time.perf_counter(), **kw)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_tiny_run_is_correct(tiny_root, capsys):
    rc, res = _run(tiny_root, capsys)
    assert rc == 0 and res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["served_tokens"]["value"] >= 24


def test_token_altered_where_produced_fails(tiny_root, capsys):
    from repro.serving.engine import Engine

    def alter_tokens(eng):
        vocab = eng.cfg.vocab_size

        def emit(toks, emitted, row_req):
            return Engine._emit((np.asarray(toks) + 1) % vocab, emitted,
                                row_req)
        eng._emit = emit

    rc, res = _run(tiny_root, capsys, mutate=alter_tokens)
    assert rc == 0 and res["correct"] is False


def test_control_fails_where_the_program_passes(tiny_root):
    cell = spec.load_cell("tiny-moe.offload.tiny", tiny_root)
    seed = 11
    out = serve.run_cell(cell, seed, 1.5, False, time.perf_counter(),
                         impl="interpret")
    ref = spec.load_reference(cell.config["reference"])
    prog = np.concatenate(ref.gaps(out["dims"], seed, out["seqs"]))
    ctrl = np.concatenate(ref.gaps(out["dims"], seed, out["seqs"],
                                   control=True))
    lim = TINY_CELL["check"]["limits"]
    assert prog.mean() <= lim["mean_gap"] < ctrl.mean()


def test_refuses_without_a_chip(tiny_root, capsys):
    rc = run.main(ARGS, root=tiny_root)
    assert rc != 0 and capsys.readouterr().out == ""


def test_refuses_without_the_program(tiny_root, capsys):
    (tiny_root / "src").unlink()
    rc = run.main(ARGS, root=tiny_root, require_chip=False)
    assert rc != 0 and capsys.readouterr().out == ""


@pytest.mark.parametrize("missing", ["BENCHMARK.json", "cell"])
def test_refuses_with_a_file_missing(tiny_root, capsys, missing):
    if missing == "cell":
        (tiny_root / "bench" / "cells" / "tiny-moe.offload.tiny.json").unlink()
    else:
        (tiny_root / "BENCHMARK.json").unlink()
    rc = run.main(ARGS, root=tiny_root, require_chip=False)
    assert rc != 0 and capsys.readouterr().out == ""
