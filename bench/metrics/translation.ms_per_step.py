"""Device time of address translation per decode step (ms): the self
time of the decode program's ops under the ``translate`` named scope
(``translate_step`` and the write-mask), plus the ``jit__scatter_delta``
executions that upload the dirty TAR/SF/flex entries, over the
``jit_serve_step`` executions of the traced window (``bench/scopes.py``).
None where the decode program carries no named scope."""
from bench import scopes


def read(run):
    red = scopes.scoped(run)
    if red is None:
        return None
    sec = red["split_s"].get("translate", 0.0) + red["scatter_s"]
    return 1e3 * sec / red["decode_n"]
