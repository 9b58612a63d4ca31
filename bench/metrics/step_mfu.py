"""Model FLOP utilization of the engine steps in the traced window: the
model operations of every prefill piece and decode row they ran (from
``bench/flops.py``), over the steps' summed host time times the chip's
peak bf16 FLOP/s."""

from bench import flops


def read(run):
    steps = run.traced_steps()
    if run.trace is None or not steps:
        return None
    shape = run.cell.shape
    work = sum((flops.prefill_flops(shape, s.prefill_len, s.prefill_start)
                if s.prefill_len else 0.0)
               + (flops.decode_flops(shape, s.decode_kv) if s.decode_kv
                  else 0.0) for s in steps)
    seconds = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * work / (seconds * run.peaks["bf16_flops"]) if work else None
