import json

import numpy as np

from conftest import REPO

from bench.traffic import Traffic, length_pool

MIX = json.loads((REPO / "bench" / "traffic" / "mtbench.json").read_text())


def _draw(seed, n=50):
    t = Traffic(MIX, seed, 32000)
    return [next(t) for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _draw(2 ** 33 + 17), _draw(2 ** 33 + 17)
    assert all(np.array_equal(pa, pb) and na == nb
               for (pa, na), (pb, nb) in zip(a, b))


def test_other_seed_other_order_same_sizes():
    n = len(length_pool(MIX))
    a, b = _draw(1, n), _draw(2, n)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert sorted((len(p), o) for p, o in a) == \
        sorted((len(p), o) for p, o in b)


def test_mix_shape():
    pool = length_pool(MIX)
    assert abs(pool[:, 0].mean() - 77) < 3
    assert pool[:, 0].min() >= 16 and pool[:, 0].max() <= 256
    assert pool[:, 1].min() >= 32 and pool[:, 1].max() <= 256
    assert len(Traffic(MIX, 0, 32000).prompt_buckets(16, 512)) <= 16


def test_prompt_ids_avoid_pad_and_eos():
    for p, _ in _draw(3, 100):
        assert p.min() >= 2 and p.max() < 32000
