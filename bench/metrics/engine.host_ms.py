"""Host time per engine step outside the device fetch (ms): the median,
over the ``engine.step`` spans inside the traced window, of each span's
length less the ``engine.fetch`` spans and the host's compile events
within it (``bench/scopes.py``).  The median, because a step that
compiles a program the warm-up missed, or whose dispatch waits behind a
long prefill on the device, lasts tens to hundreds of times the others.
None where the engine records no spans."""
import statistics

from bench import scopes


def read(run):
    red = scopes.engine_spans(run)
    if red is None:
        return None
    return 1e3 * statistics.median(red["host_step_s"])
