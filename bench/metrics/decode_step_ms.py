"""Device time of the ``_decode`` executions in the traced window, per
execution."""


def read(run):
    pairs = run.matched("_decode")
    if pairs is None:
        return None
    return 1e-6 * sum(ex.dur for _, ex in pairs) / len(pairs)
