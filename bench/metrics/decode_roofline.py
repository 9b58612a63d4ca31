"""Share of the decode roofline: for every ``_decode`` execution in the
traced window, the least time the chip could take (the larger of needed
operations over peak bf16 FLOP/s and needed bytes over HBM bandwidth,
from ``bench/flops.py``), summed, over the executions' summed device
time."""

from bench import flops


def read(run):
    pairs = run.matched("_decode")
    if pairs is None:
        return None
    shape, peak = run.cell.shape, run.peaks
    least = sum(max(flops.decode_flops(shape, st.decode_kv)
                    / peak["bf16_flops"],
                    flops.decode_bytes(shape, st.decode_kv)
                    / peak["hbm_bytes_per_s"]) for st, _ in pairs)
    busy = 1e-9 * sum(ex.dur for _, ex in pairs)
    return 100.0 * least / busy
