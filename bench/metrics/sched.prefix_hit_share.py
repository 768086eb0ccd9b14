"""Share of prompt tokens attached from the prefix cache (%): cached blocks
times block size over prompt tokens, for the requests whose prompt
finished inside the window (``Engine.stats()["per_request"]``)."""
from bench.metrics._common import cached_tokens, first_token_in


def read(run):
    recs = first_token_in(run, run.window)
    total = sum(len(r.prompt) for r in recs)
    if not total:
        return None
    return 100.0 * sum(cached_tokens(run, r) for r in recs) / total
