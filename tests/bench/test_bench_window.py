import pytest

from bench import window
from bench.window import Tick


def _log():
    # request 1 lives through all three ticks, 2 is admitted in tick 1 and
    # finishes in tick 2, 3 is live in tick 0 but receives nothing
    return [
        Tick(10.0, 11.0, {1: 8, 3: 0}, frozenset({1, 3}), frozenset(),
             [1, 0], 2),
        Tick(11.0, 13.0, {1: 8, 2: 9}, frozenset({1, 2}), frozenset({2}),
             [2, 0], 2),
        Tick(13.0, 14.0, {1: 8, 2: 3}, frozenset({1, 2}), frozenset(),
             [1, 1], 2),
    ]


def test_rate_is_all_tokens_over_all_time():
    log = _log()
    assert window.window_seconds(log) == 4.0
    assert window.generated_tokens(log) == 36
    assert window.gen_tokens_per_s(log) == 9.0


def test_tpot_per_request():
    tp = window.tpot_s(_log())
    assert tp[1] == pytest.approx(4.0 / 24)
    assert tp[2] == pytest.approx(3.0 / 12)
    assert tp[3] == 4.0              # no tokens: the whole window


def test_p95_linear():
    assert window.p95(range(21)) == pytest.approx(19.0)
    assert window.p95([5.0]) == 5.0


def test_slot_occupancy_over_chunks():
    # chunks with live rows: 1/2, 2/2, 1/2, 1/2
    assert window.slot_occupancy(_log()) == pytest.approx(0.625)
