"""Traffic from one seed is the same twice, differs between seeds, and
offers every seed the same work."""
import collections

import numpy as np
import pytest

from chipbench import manifest, traffic

BACKLOG = manifest.load_json(
    manifest.ROOT, manifest.traffic_path("backlog_p128-1024_o128-512"))
SEEDS = [0, 7, 2 ** 31 + 12345]


def _lengths(queue):
    return [(len(p), o) for p, o in queue]


@pytest.mark.parametrize("seed", SEEDS)
def test_backlog_is_identical_twice(seed):
    a = traffic.backlog(BACKLOG, 50272, seed)
    b = traffic.backlog(BACKLOG, 50272, seed)
    assert _lengths(a) == _lengths(b)
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))


def test_backlog_differs_between_seeds_in_tokens_not_in_work():
    a, b = (traffic.backlog(BACKLOG, 50272, s) for s in SEEDS[:2])
    assert _lengths(a) == _lengths(b)
    assert not np.array_equal(a[0][0][:16], b[0][0][:16])
    c = traffic.backlog(dict(BACKLOG, order_seed=1), 50272, SEEDS[0])
    assert _lengths(a) != _lengths(c)
    # another order, the same sizes
    assert sorted(p for p, _ in _lengths(a)[:64]) == \
        sorted(p for p, _ in _lengths(c)[:64])


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_and_every_block_offers_the_same_sizes(seed):
    q = traffic.backlog(BACKLOG, 50272, seed)
    block = BACKLOG["block"]
    assert len(q) == BACKLOG["requests"]
    want_p = collections.Counter(len(p) for p, _ in q[:block])
    want_o = collections.Counter(o for _, o in q[:block])
    ref = traffic.backlog(BACKLOG, 50272, SEEDS[0])
    assert want_p == collections.Counter(len(p) for p, _ in ref[:block])
    for lo in range(0, len(q), block):
        part = q[lo:lo + block]
        assert collections.Counter(len(p) for p, _ in part) == want_p
        assert collections.Counter(o for _, o in part) == want_o


def test_backlog_lengths_are_inside_the_files_bounds():
    q = traffic.backlog(BACKLOG, 50272, 3)
    plens = [len(p) for p, _ in q]
    outs = [o for _, o in q]
    assert BACKLOG["prompt_min"] <= min(plens) and \
        max(plens) <= BACKLOG["prompt_max"] <= BACKLOG["max_prefill"]
    assert BACKLOG["output_min"] <= min(outs) and \
        max(outs) <= BACKLOG["output_max"]
    assert max(plens) + max(outs) <= BACKLOG["cache_len"]
    assert all(0 <= p.min() and p.max() < 50272 for p, _ in q[:64])


def test_log_uniform_points_are_log_spaced():
    pts = traffic.log_uniform_points(128, 1024, 64)
    assert pts[0] >= 128 and pts[-1] <= 1024 and list(pts) == sorted(pts)
    # the median of a log-uniform law is the geometric mean of its bounds
    assert abs(np.median(pts) - (128 * 1024) ** 0.5) < 8


def test_backlog_refuses_a_ragged_block():
    with pytest.raises(ValueError):
        traffic.backlog(dict(BACKLOG, requests=100, block=64), 100, 0)


@pytest.mark.parametrize("kind", ["images", "tokens"])
def test_train_batches_follow_the_seed(kind):
    if kind == "images":
        t, cfg = {"batch": 2, "pool": 2}, {"image_shape": [3, 8, 8],
                                           "num_classes": 10}
    else:
        t, cfg = {"batch": 2, "pool": 2, "seq_len": 16}, {"vocab_size": 50}
    a = traffic.train_batches(t, cfg, 5)()
    b = traffic.train_batches(t, cfg, 5)()
    c = traffic.train_batches(t, cfg, 2 ** 31 + 6)()
    assert len(a) == 2
    for (da, la), (db, lb), (dc, _) in zip(a, b, c):
        assert np.array_equal(da, db) and np.array_equal(la, lb)
        assert not np.array_equal(da, dc)
    if kind == "tokens":
        d, l = np.asarray(a[0][0]), np.asarray(a[0][1])
        assert d.shape == (2, 16) and np.array_equal(d[:, 1:], l[:, :-1])
        assert l.min() >= 0 and l.max() < 50
    assert not np.array_equal(a[0][0], a[1][0])
