"""Device time of the routed-expert sublayers in the prefill programs per
thousand prompt tokens forwarded (ms): self time under the ``moe_*`` scopes
in ``jit_prefill_step`` and ``jit_prefix_prefill_step`` in the traced
window, over the prompt tokens (less those attached from the cache) of the
requests whose prompt finished in that window, as ``prefill.ms_per_ktok``
counts them."""
from bench import moe_scopes
from bench.metrics._common import cached_tokens, first_token_in


def read(run):
    red = moe_scopes.for_run(run)
    if red is None or not red["prefill_s"]:
        return None
    recs = first_token_in(run, run.trace_window)
    tokens = sum(len(r.prompt) - cached_tokens(run, r) for r in recs)
    if not tokens:
        return None
    return 1e3 * sum(red["prefill_s"].values()) / (tokens / 1000.0)
