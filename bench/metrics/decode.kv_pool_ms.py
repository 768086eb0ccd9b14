"""Device time per decode step of the layer scan's per-layer K/V pool
slices and write-backs (ms): self time of the decode program's ops under
the ``kv_pool`` named scope over the ``jit_serve_step`` executions of the
traced window (``bench/scopes.py``).  Ops XLA adds without a name stack
(``copy`` of a slice, for one) fall under ``unscoped``, not here.  None
where the decode program carries no named scope."""
from bench import scopes


def read(run):
    red = scopes.scoped(run)
    if red is None:
        return None
    return 1e3 * red["split_s"].get("kv_pool", 0.0) / red["decode_n"]
