"""The traffic generator: deterministic per seed, clipped, and the same work
for every seed; arrival processes found by name under bench/arrivals/."""

import hashlib
import json
import struct

import numpy as np
import pytest
from conftest import REPO

from bench import traffic
from bench.arrivals import backlog

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


# sha256 over (due, max_new, prompt length, prompt ids) of every request of
# bench/traffic/chat.json at 51 s and vocab 49152: the chat cell's traffic,
# request for request and byte for byte.  A change to the Poisson process or
# the shared helpers that moves it changes what the chat cell measures.
CHAT_SIGNATURES = {
    2 ** 33 + 7:
        "b85d391a7a0d17a27255686a09763d96a49e092314fca0fcefe99cc67e0adb69",
    2200001601:
        "c87974ca1b3e63bb1795ed0f54ac85dd5e73a35dc4748d3c27e8c5e7eeeff673",
}
DOCBATCH_MAX_SEQ = 8192        # the slots of the cell that will serve it


def _signature(items) -> str:
    h = hashlib.sha256()
    for it in items:
        h.update(struct.pack("<dqq", it.due, it.max_new, it.prompt.size))
        h.update(it.prompt.astype("<i4").tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(CHAT_SIGNATURES))
def test_chat_traffic_is_unchanged(seed):
    items = traffic.generate(MIXES["chat"], seed, 51.0, 49152)
    assert len(items) == 182
    assert _signature(items) == CHAT_SIGNATURES[seed]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_mix_names_an_arrival_file(mix):
    assert (traffic.ARRIVALS / f"{MIXES[mix]['arrivals']}.py").is_file()


def test_unknown_arrival_process_raises_and_names_the_files():
    mix = dict(MIXES["chat"], arrivals="gamma")
    with pytest.raises(ValueError, match="gamma") as err:
        traffic.generate(mix, 1, 10.0, 100)
    for known in ("backlog", "poisson"):
        assert known in str(err.value)


def test_backlog_is_due_before_the_window():
    """Every request is due when the pre-roll starts, and the longest
    prompt with the longest answer fits the slots of the coming cell."""
    m = MIXES["docbatch"]
    items = traffic.generate(m, 2 ** 33 + 9, 51.0, 65024)
    assert len(items) == m["count"] == 1024
    assert all(it.due == -m["preroll_s"] for it in items)
    prompts = np.array([it.prompt.size for it in items])
    answers = np.array([it.max_new for it in items])
    assert m["prompt"]["max"] + m["output"]["max"] < DOCBATCH_MAX_SEQ
    assert np.max(prompts + answers) < DOCBATCH_MAX_SEQ
    assert abs(np.median(prompts) - m["prompt"]["median"]) <= 2
    assert abs(np.median(answers) - m["output"]["median"]) <= 2


def test_backlog_seeds_draw_only_the_prompt_ids():
    """The window serves the front of the queue: every seed replays the
    same lengths in the same order, and the window's length changes
    nothing."""
    m = MIXES["docbatch"]
    a = traffic.generate(m, 2 ** 33 + 11, 51.0, 65024)
    b = traffic.generate(m, 12, 51.0, 65024)
    for key in (lambda it: it.prompt.size, lambda it: it.max_new):
        assert list(map(key, a)) == list(map(key, b))
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert _signature(traffic.generate(m, 12, 3.0, 65024)) == _signature(b)


def test_backlog_front_holds_every_band():
    """Every aligned run of 2**j requests holds one prompt length of each
    band of ``count / 2**j`` consecutive quantiles, so whatever number of
    documents a window reaches spans the distribution; answers, in the
    order of base 3, hold one of each third in every aligned run of three,
    and are not ranked with the prompts."""
    m = MIXES["docbatch"]
    items = traffic.generate(m, 2 ** 33 + 13, 51.0, 65024)
    n = m["count"]
    prompts = np.array([it.prompt.size for it in items])
    answers = np.array([it.max_new for it in items])
    qp = traffic.quantile_lengths(m["prompt"], n)
    qo = traffic.quantile_lengths(m["output"], n)
    for j in range(1, 11):
        b = 2 ** j
        bands = qp.reshape(b, n // b)
        for i in range(0, n, b):
            block = sorted(prompts[i:i + b])
            assert all(bands[k, 0] <= v <= bands[k, -1]
                       for k, v in enumerate(block))
    ranks = backlog.halton_ranks(n, 3)
    assert list(answers) == list(qo[ranks])
    # the radical inverse's first digit is the position's last in base 3
    edges = np.cumsum([len(range(k, n, 3)) for k in range(3)])
    for i in range(0, n - n % 3, 3):
        thirds = sorted(np.searchsorted(edges, ranks[i:i + 3], side="right"))
        assert thirds == [0, 1, 2]
    assert abs(np.corrcoef(prompts[:64], answers[:64])[0, 1]) < 0.2


@pytest.mark.parametrize("bad", [dict(count=0), dict(preroll_s=0.0)])
def test_backlog_needs_requests_and_a_preroll(bad):
    with pytest.raises(ValueError, match="backlog"):
        traffic.generate(dict(MIXES["docbatch"], **bad), 1, 10.0, 100)
