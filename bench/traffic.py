"""Seeded traffic from a mix file: one generator for every mix.

A mix (``bench/traffic/<name>.json``) gives the arrival process and the
length distributions.  The seed never changes the work the window sees:
the lengths and due times are a fixed multiset (midpoint quantiles of the
distributions), and the prompt token ids are drawn from the seed.

The mix's ``arrivals`` names an arrival process,
``bench/arrivals/<arrivals>.py``, so that a new process is a new file and
never an edit here.  Its ``generate(mix, rng, seconds, vocab)`` returns
the run's :class:`Item` list, every due time relative to the window's
opening (the pre-roll is before 0); it offers the window the same work
for every seed, draws prompt ids from ``rng``, and uses the shared helpers
below.  Where the window sees all of its part of the traffic, as with
open-loop arrivals, the seed may also draw the order: ``poisson``
reorders one multiset of requests in the pre-roll and one in the window.
Where it sees only a part, as with a backlog whose window serves the front
of the queue, the order is fixed, since the seed would otherwise choose
the part.  With ``strata`` k > 1, :func:`stratified` draws an order in
which each quantity's values are split into k bands (strata) of equal
count, and every k consecutive requests hold one value of each band, so
that no stretch of the window is much more loaded than another and the
tails do not swing with where the seed puts the long prompts and short
gaps.  Within a block of k the order is random.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

from . import load_module

__all__ = ["Item", "generate", "quantile_lengths", "stratified"]

ARRIVALS = Path(__file__).resolve().parent / "arrivals"


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
    name = mix["arrivals"]
    path = ARRIVALS / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in ARRIVALS.glob("*.py"))
        raise ValueError(f"unknown arrival process {name!r}; "
                         f"bench/arrivals/ has {known}")
    items = load_module(path).generate(mix, np.random.default_rng(seed),
                                       float(seconds), vocab)
    return sorted(items, key=lambda it: it.due)
