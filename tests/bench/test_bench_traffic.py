"""The traffic generator: the same seed gives the same requests, and every
seed gets the same sizes, arrivals and repeats in the same order."""
import numpy as np
import pytest

from bench import traffic

FEAT = np.random.default_rng(0).standard_normal((6000, 8)).astype(np.float32)
OPEN = {"loop": "open", "rate_rps": 5.0,
        "output": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                   "min": 16, "max": 256},
        "queries": {"kind": "unique", "noise": 0.1}, "shape_seed": 0}
ZIPF = {"loop": "closed", "clients": 4,
        "output": {"dist": "fixed", "tokens": 16},
        "queries": {"kind": "zipf", "s": 1.1, "distinct": 64, "noise": 0.1},
        "shape_seed": 0}
BIG = 2 ** 33 + 17  # seeds past 32 bits


def _draw(mix, seed, n=traffic.BLOCK, **kw):
    return traffic.Stream(mix, FEAT, seed, **kw).take(n)


@pytest.mark.parametrize("mix", [OPEN, ZIPF], ids=["open", "zipf"])
def test_same_seed_same_requests(mix):
    a, b = _draw(mix, BIG, 300), _draw(mix, BIG, 300)
    assert [(s.node, s.max_new, s.due) for s in a] == \
        [(s.node, s.max_new, s.due) for s in b]
    assert all(np.array_equal(x.query, y.query) for x, y in zip(a, b))


def _repeats(reqs):
    """Each request's first position among requests of its node."""
    first = {}
    return [first.setdefault(s.node, i) for i, s in enumerate(reqs)]


def test_seeds_share_sizes_and_arrivals_in_another_order():
    """Seeds differ only in the queries: sizes, arrivals and the pattern of
    repeats come in one order for every seed."""
    a, b = _draw(OPEN, 1), _draw(OPEN, BIG)
    assert [s.max_new for s in a] == [s.max_new for s in b]
    assert [s.due for s in a] == [s.due for s in b]
    assert a[0].due == 0.0
    assert {s.node for s in a} != {s.node for s in b}
    z1, z2 = _draw(ZIPF, 1), _draw(ZIPF, BIG)
    assert _repeats(z1) == _repeats(z2)
    assert [s.node for s in z1] != [s.node for s in z2]


def test_lognormal_sizes_are_clipped_and_near_the_median():
    sizes = np.array([s.max_new for s in _draw(OPEN, 3)])
    assert sizes.min() >= 16 and sizes.max() <= 256
    assert 40 <= np.median(sizes) <= 56


def test_open_loop_rate():
    due = [s.due for s in _draw(OPEN, 4)]
    assert len(due) / due[-1] == pytest.approx(5.0, rel=0.05)
    assert all(b > a for a, b in zip(due, due[1:]))


def test_a_block_spans_its_window_under_every_seed():
    """A window holds the same arrivals and sizes under every seed, and a
    run that outlasts a block of draws goes on from the same schedule."""
    mix = dict(OPEN, rate_rps=2.0)
    ref = [(s.due, s.max_new) for s in _draw(mix, 1, traffic.BLOCK + 60)]
    assert ref[traffic.BLOCK][0] > ref[traffic.BLOCK - 1][0]
    for seed in (2, BIG):
        reqs = _draw(mix, seed, traffic.BLOCK + 60)
        assert [(s.due, s.max_new) for s in reqs] == ref
        inside = [s for s in reqs if s.due < 30.0]
        assert 40 <= len(inside) <= 80 and inside[0].due == 0.0


def test_unique_queries_never_repeat_a_node():
    nodes = [s.node for s in _draw(OPEN, 5)]
    assert len(set(nodes)) == len(nodes)
    s = _draw(OPEN, 5, 1)[0]
    assert np.abs(s.query - FEAT[s.node]).max() < 1.0


def test_zipf_repeats_are_exact_and_skewed():
    reqs = _draw(ZIPF, 6)
    by_node = {}
    for s in reqs:
        by_node.setdefault(s.node, []).append(s.query)
    assert len(by_node) <= 64
    assert all(all(np.array_equal(q[0], x) for x in q)
               for q in by_node.values())
    counts = sorted((len(v) for v in by_node.values()), reverse=True)
    assert counts[0] > 10 * counts[len(counts) // 2]


def test_warmup_stream_differs_from_the_window():
    a = _draw(OPEN, 7, 50)
    w = _draw(OPEN, 7, 50, warmup=True)
    assert [s.node for s in a] != [s.node for s in w]
