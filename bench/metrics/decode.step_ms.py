"""Device time per decode program (ms): the ``jit_serve_step`` executions
of the traced window, their device seconds over their count."""
from bench.metrics._common import decode_device


def read(run):
    sec, n = decode_device(run)
    return 1e3 * sec / n if n else None
