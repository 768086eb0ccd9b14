"""Shared arithmetic of the per-layer readers (not a metric itself)."""
from __future__ import annotations

from bench import roofline, trace

DECODE = r"^jit_serve_step"
PREFILL = r"^jit_(prefill_step|prefix_prefill_step|prefix_step)"


def in_window(t: float, window) -> bool:
    return window is not None and window[0] <= t < window[1]


def first_token_in(run, window):
    """Requests whose prompt finished (first token) inside ``window``."""
    return [r for r in run.client.recs.values()
            if r.rid >= 0 and r.tokens and in_window(r.times[0], window)]


def cached_tokens(run, rec) -> int:
    row = run.stats["per_request"].get(rec.rid)
    return 0 if row is None else int(row["cached_blocks"]) * run.dims.block


def decode_polls(run, window):
    return [p for p in run.client.polls
            if p.decode_rows and in_window(p.t0, window)
            and p.t1 <= window[1]]


def decode_device(run):
    """(device seconds, executions) of the decode programs in the trace."""
    if run.trace is None:
        return 0.0, 0
    return trace.module_time(run.trace, DECODE)


def prefill_flops(run, recs) -> float:
    return sum(roofline.prefill_flops(run.dims, cached_tokens(run, r),
                                      len(r.prompt)) for r in recs)
