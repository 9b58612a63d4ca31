"""An offline batch: ``count`` requests, all due when the pre-roll starts
(``-preroll_s``), none in the window, as a job that submits a whole corpus
at once.

A window serves only the front of the queue, a few tens of documents, so
the queue's order is fixed and the seed draws the prompt ids alone: every
seed replays the same documents, and the window's work does not move with
the seed.  The lengths are the ``count`` midpoint quantiles of the mix's
distributions, in the order of a Halton sequence (prompts by base 2,
answers by base 3): any run of requests from the front holds both
distributions evenly and pairs them as if drawn apart, however far a
window reaches.  The window then measures an engine whose queue does not
empty, so long as ``count`` outlasts the pre-roll and the window."""

import numpy as np

from bench.traffic import Item, quantile_lengths


def halton_ranks(n: int, base: int) -> np.ndarray:
    """Rank, among ``0..n-1``, of the radical inverse in ``base`` of each
    position ``0..n-1``: every aligned run of ``base**j`` positions holds
    one rank of each of ``base**j`` bands where ``base**j`` divides ``n``."""
    keys = []
    for i in range(n):
        f, x = 1.0, 0.0
        while i:
            f /= base
            x += f * (i % base)
            i //= base
        keys.append(x)
    return np.argsort(np.argsort(keys))


def generate(mix: dict, rng, seconds: float, vocab: int) -> list:
    n, preroll = int(mix["count"]), float(mix["preroll_s"])
    if n < 1 or preroll <= 0:
        raise ValueError(f"a backlog needs count >= 1 and preroll_s > 0, "
                         f"not {n} and {preroll}: its requests are due "
                         f"before the window opens")
    prompts = quantile_lengths(mix["prompt"], n)[halton_ranks(n, 2)]
    outputs = quantile_lengths(mix["output"], n)[halton_ranks(n, 3)]
    return [Item(due=-preroll,
                 prompt=rng.integers(0, vocab, size=int(p), dtype=np.int32),
                 max_new=int(o))
            for p, o in zip(prompts, outputs)]
