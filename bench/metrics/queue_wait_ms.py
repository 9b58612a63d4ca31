"""90th percentile of how long the requests admitted in the window waited
in the engine's queue: from the engine's host-clock stamp at submit
(``Request.host_queued``) to its stamp when the prefill lane took the
request (``host_admitted``).  A 90th percentile needs ten requests beyond
it, so fewer than 100 read nothing; so does a program that does not stamp
requests."""

import numpy as np

MIN_REQUESTS = 100


def read(run):
    waits = []
    for tr in run.requests:
        queued = getattr(tr.req, "host_queued", None)
        admitted = getattr(tr.req, "host_admitted", None)
        if queued is not None and run.in_window(admitted):
            waits.append(admitted - queued)
    if len(waits) < MIN_REQUESTS:
        return None
    return 1e3 * float(np.percentile(waits, 90))
