"""95th percentile of every gap between two consecutive tokens of one
request, received in the window (two tokens of one step: a gap of 0)."""

import numpy as np


def read(run):
    gaps = [b - a for tr in run.requests
            for a, b in zip(tr.token_t, tr.token_t[1:]) if run.in_window(b)]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
