"""The traffic generator: deterministic per seed, clipped, and the same work
for every seed."""

import json

import numpy as np
import pytest
from conftest import REPO

from bench import traffic

MIXES = {p.stem: json.loads(p.read_text())
         for p in sorted((REPO / "bench" / "traffic").glob("*.json"))}


def _sig(items):
    return [(it.due, it.max_new, it.prompt.tobytes()) for it in items]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_traffic(mix):
    a = traffic.generate(MIXES[mix], 2 ** 33 + 7, 20.0, 49152)
    b = traffic.generate(MIXES[mix], 2 ** 33 + 7, 20.0, 49152)
    c = traffic.generate(MIXES[mix], 2 ** 33 + 8, 20.0, 49152)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_clips_and_vocab(mix):
    m = MIXES[mix]
    items = traffic.generate(m, 5, 20.0, 1000)
    lens = [it.prompt.size for it in items]
    outs = [it.max_new for it in items]
    assert m["prompt"]["min"] <= min(lens) and max(lens) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(outs) and max(outs) <= m["output"]["max"]
    assert all(0 <= it.prompt.min() and it.prompt.max() < 1000
               for it in items)


def _gaps(items, seconds):
    """The pre-roll's and the window's gaps, each part's end included."""
    pre = [it.due for it in items if it.due < 0]
    win = [it.due for it in items if it.due >= 0]
    return np.diff(pre + [0.0]), np.diff(win + [seconds])


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_work(mix):
    """Seeds reorder one multiset of lengths and gaps, in the pre-roll and
    in the window apart."""
    a = traffic.generate(MIXES[mix], 1, 20.0, 100)
    b = traffic.generate(MIXES[mix], 99, 20.0, 100)
    for part in (lambda it: it.due < 0, lambda it: it.due >= 0):
        for key in (lambda it: it.prompt.size, lambda it: it.max_new):
            assert (sorted(key(it) for it in a if part(it))
                    == sorted(key(it) for it in b if part(it)))
    for ga, gb in zip(_gaps(a, 20.0), _gaps(b, 20.0)):
        np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)


def test_poisson_spans_preroll_and_window():
    m = MIXES["chat"]
    items = traffic.generate(m, 3, 30.0, 100)
    due = np.array([it.due for it in items])
    assert len(items) == (round(m["rate"] * m["preroll_s"])
                          + round(m["rate"] * 30.0))
    assert due[0] == -m["preroll_s"] and np.all(np.diff(due) > 0)
    assert np.count_nonzero(due == 0.0) == 1 and due[-1] < 30.0
    # the median of the lengths is the mix's median
    lens = [it.prompt.size for it in items]
    assert abs(np.median(lens) - m["prompt"]["median"]) <= 2


@pytest.mark.parametrize("k", [1, 3, 4])
def test_stratified_blocks_hold_one_value_of_each_band(k):
    """Every k consecutive values hold one of each band of n/k; the order
    is a permutation drawn from the seed."""
    values = np.arange(22, dtype=float)
    a = traffic.stratified(values, k, np.random.default_rng(1))
    b = traffic.stratified(values, k, np.random.default_rng(2))
    assert sorted(a) == sorted(values) and list(a) != list(b)
    blocks = -(-len(values) // k)
    whole = blocks - (k * blocks - len(values))    # the short ones are last
    for i in range(whole):
        block = a[i * k:(i + 1) * k]
        assert sorted(int(v) // blocks for v in block) == list(range(k))


def test_prompt_and_answer_fit_the_cells_cache():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        mix = json.loads((REPO / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        cell = json.loads((REPO / "bench" / "cells"
                           / f"{w['name']}.json").read_text())
        assert mix["prompt"]["max"] + mix["output"]["max"] < cell["max_seq"]
