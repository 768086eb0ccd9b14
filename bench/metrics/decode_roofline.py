"""Share of the roofline reached by the decode program (%): the least time
a decode step needs (the larger of its FLOPs over peak and its bytes over
HBM bandwidth, counted from the live rows' contexts by
``roofline.decode_step_need``), over the device time of a decode program,
both averaged per step over the traced window."""
from bench import roofline
from bench.metrics._common import decode_device, decode_polls


def read(run):
    sec, n = decode_device(run)
    polls = decode_polls(run, run.trace_window)
    if not n or not polls or run.peaks is None:
        return None
    least = sum(roofline.least_time(p.decode_flops, p.decode_bytes, run.peaks)
                for p in polls) / len(polls)
    return 100.0 * least / (sec / n)
