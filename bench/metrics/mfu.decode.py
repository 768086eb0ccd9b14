"""Model FLOP utilization of the decode program (%): the model FLOPs of a
decode step's live rows (weights, LM head, attention over each context),
over its device time times the chip's bf16 peak, per step over the traced
window.  It bounds what any kernel inside the decode step can claim."""
from bench.metrics._common import decode_device, decode_polls


def read(run):
    sec, n = decode_device(run)
    polls = decode_polls(run, run.trace_window)
    if not n or not polls or run.peaks is None:
        return None
    flops = sum(p.decode_flops for p in polls) / len(polls)
    return 100.0 * flops / ((sec / n) * run.peaks["bf16_flops_per_s"])
