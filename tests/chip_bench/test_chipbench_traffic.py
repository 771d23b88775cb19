"""The general traffic generator: deterministic from the seed, with the
stated length, modality and repeat distributions, and the same work for
every seed in a different order."""
from __future__ import annotations

import collections

import numpy as np
import pytest

import _tiny  # noqa: F401  (puts the benchmark on the path)
from _tiny import load
from chipbench import traffic as tf

MIO = load("traffic/miobench-open.json")
CLOSED = load("traffic/decode-closed.json")
D, V = 32, 1000


def open_items(seed, seconds=60.0, rate=None):
    return tf.Traffic(MIO, seed=seed, seconds=seconds, vocab=V, d_model=D,
                      max_batch=64, rate_per_s=rate).items


def test_open_loop_is_deterministic_from_the_seed():
    a, b = open_items(2**33 + 1), open_items(2**33 + 1)
    assert [(x.due, x.max_new, x.task, x.ask) for x in a] == \
        [(x.due, x.max_new, x.task, x.ask) for x in b]
    assert all(np.array_equal(x.text, y.text) for x, y in zip(a, b))
    c = open_items(2**33 + 2)
    assert [x.due for x in a] != [x.due for x in c]


def test_open_loop_rate_and_window():
    items = open_items(5, seconds=100.0, rate=10.0)
    dues = np.array([x.due for x in items])
    assert np.all(np.diff(dues) >= 0) and dues.max() < 100.0
    assert 900 <= len(items) <= 1100  # 10 req/s for 100 s


def test_same_sizes_for_every_seed():
    """A fixed set of asks: two seeds send the same sizes, kinds and asks
    per task, in a different order."""
    def work(seed):
        items = tf.Traffic(MIO, seed=seed, seconds=60.0, vocab=V, d_model=D,
                           max_batch=64).items
        asks = collections.Counter(x.task for x in items)
        return (sorted(x.max_new for x in items),
                sorted((x.media_kind or "", len(x.text)) for x in items
                       if x.ask == 0),
                sorted((x.media_kind or "", asks[x.task]) for x in items
                       if x.ask == 0))
    a, b = work(1), work(2)
    assert a == b
    assert len(a[0]) == round(MIO["arrivals"]["rate_per_s"] * 60.0)


def test_each_kind_share_of_the_asks_is_exact():
    items = open_items(3, seconds=100.0, rate=4.0)
    assert len(items) == 400
    first = [x for x in items if x.ask == 0]
    kinds = collections.Counter(x.media_kind for x in first)
    asks = collections.Counter(x.media_kind for x in items)
    # each ask count 1-4 in turn: 160 tasks, 40 of each count
    assert len(first) == 160
    assert kinds == {"image": 112, "audio": 24, None: 24}
    assert asks == {"image": 280, "audio": 60, None: 60}


def test_modality_lengths_and_repeats():
    items = open_items(7, seconds=200.0)
    by_task = collections.defaultdict(list)
    for x in items:
        by_task[x.task].append(x)
    kinds = collections.Counter(xs[0].media_kind for xs in by_task.values())
    n = sum(kinds.values())
    assert abs(kinds["image"] / n - 0.70) < 0.05
    assert abs(kinds["audio"] / n - 0.15) < 0.05
    rows = {"image": 197, "audio": 1500}
    delays = []
    for xs in by_task.values():
        assert len(xs) <= 4
        first = min(xs, key=lambda x: x.ask)
        for x in xs:
            assert 8 <= len(x.text) <= 128 and 8 <= x.max_new <= 256
            assert len(x.prefix) == 64 and x.prefix is items[0].prefix
            if x.media is not None:
                assert x.media.shape == (rows[x.media_kind], D)
                # a repeat carries the same media bytes (same digest)
                assert x.media is first.media
            # later asks rotate the text by one token per ask
            assert np.array_equal(x.text, np.roll(first.text, x.ask - first.ask))
            # a repeat is due up to 10 s after the first ask and takes the
            # first free arrival from then on
            assert x.due - first.due <= 10.0 + 3.0
            if x.ask:
                delays.append(x.due - first.due)
    assert 4.0 <= np.mean(delays) <= 7.0  # uniform over 10 s, then queued
    out = np.array([x.max_new for x in items])
    assert 40 <= np.median(out) <= 56  # median 48
    asks = collections.Counter(len(xs) for xs in by_task.values())
    assert set(asks) <= {1, 2, 3, 4} and len(asks) == 4
    longest = max(x.prompt_len + x.max_new for x in items)
    assert longest <= 2047  # fits max_seq 2048


def test_closed_loop_streams_and_residual_first_requests():
    def stream(seed, clients):
        tr = tf.Traffic(CLOSED, seed=seed, seconds=30.0, vocab=V, d_model=D,
                        max_batch=64)
        assert tr.clients == 128
        return tr, {c: [tr.next_for(c) for _ in range(3)]
                    for c in clients}
    # clients finish in any order: a client's k-th request is fixed
    tr, a = stream(11, range(128))
    _, b = stream(11, reversed(range(128)))
    for c in range(tr.clients):
        for x, y in zip(a[c], b[c]):
            assert np.array_equal(x.text, y.text) and x.max_new == y.max_new
    firsts = np.array([a[c][0].max_new for c in a])
    later = np.array([a[c][k].max_new for c in a for k in (1, 2)])
    assert later.min() >= 256 and later.max() <= 640
    # the residual of U[256, 640] is spread over 1..640, mean ~ 233
    assert firsts.min() < 100 and 180 < firsts.mean() < 290
    lens = np.array([len(a[c][k].text) for c in a for k in range(3)])
    assert lens.min() >= 64 and lens.max() <= 1280
    assert 470 <= np.median(lens) <= 560


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "lognormal", "median": 48, "sigma": 0.6, "min": 8, "max": 256},
     8, 256),
    ({"dist": "uniform", "min": 256, "max": 640}, 256, 640)])
def test_quantiles_cover_the_distribution(dist, lo, hi):
    q = tf.quantiles(dist, tf._u(1000))
    assert q.min() >= lo and q.max() <= hi
    assert np.all(np.diff(q) >= 0)
