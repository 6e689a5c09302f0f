"""Readers of the program's own counters: expert-span reads counted in
the serving programs and host seconds of the paging layers' spans, read
as window deltas of ``weight_traffic()`` / ``kv_traffic()``.  A program
without those keys reads as no metric."""
from types import SimpleNamespace

import pytest

from bench import spec
from bench.window import Tick

# 36 generated tokens over the window
TICKS = [Tick(0.0, 1.0, {1: 20}, frozenset({1}), frozenset(), [1], 2),
         Tick(1.0, 2.0, {1: 16}, frozenset({1}), frozenset(), [1], 2)]
WEIGHT = ({"read_spans": 10, "read_bytes": 3_520, "pool_reads": 4,
           "host_s": 0.5, "expert_bytes": 352},
          {"read_spans": 82, "read_bytes": 28_864, "pool_reads": 22,
           "host_s": 0.59, "expert_bytes": 704})
KV = ({"mode": "kv_paged", "host_s": 1.25, "h2d_bytes": 0},
      {"mode": "kv_paged", "host_s": 1.322, "h2d_bytes": 0})


def _run(weight=WEIGHT, kv=KV, ticks=TICKS):
    return SimpleNamespace(weight=weight, kv=kv, ticks=ticks)


@pytest.mark.parametrize("name,want", [
    ("expert_read_bytes_per_token", (28_864 - 3_520) / 36),
    ("expert_pool_read_share", 18 / (18 + 72)),
    ("expert_host_ms_per_token", 1000 * 0.09 / 36),
    ("kv_host_ms_per_token", 1000 * 0.072 / 36),
])
def test_reader_arithmetic(name, want):
    assert spec.load_metric_reader(name)(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "expert_read_bytes_per_token", "expert_pool_read_share",
    "expert_host_ms_per_token", "kv_host_ms_per_token"])
def test_reader_is_silent_without_the_keys(name):
    """The parent's engine has none of the keys: no metric, no error."""
    old_w = ({"expert_bytes": 352, "hits": 1, "misses": 1},) * 2
    old_kv = ({"mode": "kv_paged", "h2d_bytes": 0},) * 2
    read = spec.load_metric_reader(name)
    assert read(_run(weight=old_w, kv=old_kv)) is None


@pytest.mark.parametrize("name", [
    "expert_read_bytes_per_token", "expert_host_ms_per_token",
    "kv_host_ms_per_token"])
def test_per_token_reader_is_silent_without_tokens(name):
    assert spec.load_metric_reader(name)(_run(ticks=[])) is None


def test_pool_share_is_silent_without_reads():
    w = ({"read_spans": 3, "pool_reads": 2},) * 2
    read = spec.load_metric_reader("expert_pool_read_share")
    assert read(_run(weight=w)) is None
