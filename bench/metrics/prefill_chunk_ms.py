"""Device time of the full-chunk ``_prefill`` executions in the traced
window, per execution."""


def read(run):
    pairs = run.matched("_prefill")
    if pairs is None:
        return None
    full = [ex.dur for st, ex in pairs
            if st.prefill_len == run.cell.engine["prefill_chunk"]]
    return 1e-6 * sum(full) / len(full) if full else None
