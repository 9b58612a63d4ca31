"""Open-loop Poisson arrivals: exponential gaps at ``rate`` req/s, from
``-preroll_s`` to 0 and from 0 to the end of the window.

The pre-roll and the window each get a fixed multiset of gaps (midpoint
quantiles of the exponential, scaled to the part's span), prompt lengths
and answer lengths, stratified by ``strata``, in an order drawn from the
seed; so every seed offers the window the same requests."""

import numpy as np

from bench.traffic import Item, quantile_lengths, stratified


def _exponential_gaps(n: int, span: float) -> np.ndarray:
    """``n`` midpoint quantiles of an exponential, in increasing order,
    scaled to sum to ``span``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * span / q.sum()


def generate(mix: dict, rng, seconds: float, vocab: int) -> list:
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
