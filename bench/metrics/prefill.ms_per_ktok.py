"""Device time of the prefill programs per thousand prompt tokens
forwarded (ms): ``jit_prefill_step`` and ``jit_prefix_prefill_step`` in the
traced window, over the prompt tokens (less those attached from the cache)
of the requests whose prompt finished in that window."""
from bench import trace
from bench.metrics._common import PREFILL, cached_tokens, first_token_in


def read(run):
    if run.trace is None:
        return None
    sec, n = trace.module_time(run.trace, PREFILL)
    recs = first_token_in(run, run.trace_window)
    tokens = sum(len(r.prompt) - cached_tokens(run, r) for r in recs)
    if not n or not tokens:
        return None
    return 1e3 * sec / (tokens / 1000.0)
