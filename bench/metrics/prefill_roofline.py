"""Share of the prefill roofline: for every full-chunk ``_prefill``
execution in the traced window, the least time the chip could take (the
larger of the piece's needed operations over peak bf16 FLOP/s and its
needed bytes over HBM bandwidth: every weight once, and the K/V of the
positions it attends to, the piece's own included), from
``bench/flops.py``, summed, over the executions' summed device time.  A
piece's depth is the step's ``prefill_start``."""

from bench import flops


def read(run):
    pairs = run.matched("_prefill")
    if pairs is None:
        return None
    shape, peak = run.cell.shape, run.peaks
    chunk = run.cell.engine["prefill_chunk"]
    full = [(st, ex) for st, ex in pairs if st.prefill_len == chunk]
    if not full:
        return None
    least = sum(max(flops.prefill_flops(shape, chunk, st.prefill_start)
                    / peak["bf16_flops"],
                    (flops.weight_bytes(shape)
                     + flops.kv_bytes_per_token(shape)
                     * (st.prefill_start + chunk))
                    / peak["hbm_bytes_per_s"]) for st, _ in full)
    busy = 1e-9 * sum(ex.dur for _, ex in full)
    return 100.0 * least / busy
