"""95th percentile time to first token over every request due in the
window: from its due time to the host's receipt of its first token
(requests still without one at the close are followed until they get
it)."""

import numpy as np


def read(run):
    ttft = [tr.token_t[0] - tr.due for tr in run.requests
            if run.in_window(tr.due) and tr.token_t]
    return 1e3 * float(np.percentile(ttft, 95)) if ttft else None
