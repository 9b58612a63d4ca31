"""Seeded traffic from a mix file: one generator for every mix.

A mix (``bench/traffic/<name>.json``) gives the arrival process and the
length distributions.  The seed picks the order, not the work: the
pre-roll and the window each get a fixed multiset of prompt lengths,
answer lengths and inter-arrival gaps (midpoint quantiles of the
distributions), put in an order drawn from the seed, and prompt token ids
drawn from the seed.  Two seeds therefore offer the window the same
requests and differ only in how they are interleaved.

Arrivals (``poisson``): exponential gaps at ``rate`` req/s, from
``-preroll_s`` to 0 and from 0 to the end of the window; due times are
relative to the window's opening.  With ``strata`` k > 1 the order is
stratified: each quantity's values are split into k bands (strata) of
equal count, and every k consecutive requests hold one value of each band,
so that no stretch of the window is much more loaded than another and the
tails do not swing with where the seed puts the long prompts and short
gaps.  Within a block of k the order is random.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Item", "generate", "quantile_lengths", "stratified"]


@dataclass(frozen=True)
class Item:
    """One request as the generator offers it."""

    due: float            # seconds relative to the window's opening
    prompt: np.ndarray    # (prompt_len,) int32 token ids
    max_new: int


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles of ``spec``'s distribution,
    rounded and clipped to ``[min, max]``, in increasing order."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _exponential_gaps(n: int, span: float) -> np.ndarray:
    """``n`` midpoint quantiles of an exponential, in increasing order,
    scaled to sum to ``span``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * span / q.sum()


def stratified(values: np.ndarray, k: int, rng) -> np.ndarray:
    """``values`` (increasing) in an order drawn from ``rng`` in which every
    ``k`` consecutive positions hold one value of each of ``k`` bands of
    consecutive values; where the bands are short, the last blocks are."""
    n = len(values)
    blocks = -(-n // k)
    grid = np.full(blocks * k, np.nan)
    grid[:n] = values
    bands = []
    for band in grid.reshape(k, blocks):
        m = int(np.count_nonzero(~np.isnan(band)))
        bands.append(np.concatenate([band[:m][rng.permutation(m)], band[m:]]))
    order = np.concatenate([block[rng.permutation(k)]
                            for block in np.stack(bands).T])
    return order[~np.isnan(order)]


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run, sorted by due time."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rng = np.random.default_rng(seed)
    k = int(mix.get("strata", 1))
    preroll = float(mix.get("preroll_s", 0.0))
    items = []
    for start, span in ((-preroll, preroll), (0.0, float(seconds))):
        n = int(round(mix["rate"] * span))
        if n == 0:
            continue
        gaps = stratified(_exponential_gaps(n, span), k, rng)
        due = start + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        prompts = stratified(quantile_lengths(mix["prompt"], n), k, rng)
        outputs = stratified(quantile_lengths(mix["output"], n), k, rng)
        items += [Item(due=float(due[i]),
                       prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                           dtype=np.int32),
                       max_new=int(outputs[i]))
                  for i in range(n)]
    return items
