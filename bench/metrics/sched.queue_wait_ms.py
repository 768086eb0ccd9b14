"""Median time a request waited in the engine's queue (ms):
``queue_wait_s`` (submit to first prompt chunk admitted) of the requests
whose ``admitted_at`` lies inside the window (``Engine.stats()
["per_request"]``, both on ``time.perf_counter``, the client's clock).
The median, because every request that arrives while the engine compiles
a program the warm-up missed waits out the compile, seconds against
milliseconds.  Prints the mean beside it and, for the requests admitted
in the traced part, the mean wait of those whose wait overlaps a compile
on the host and of the rest.  None where the engine does not report the
waits."""
import statistics

from bench import scopes
from bench.metrics._common import in_window


def _admitted(run, window):
    return [(row["admitted_at"] - row["queue_wait_s"], row["admitted_at"])
            for rid, row in run.stats["per_request"].items()
            if rid >= 0 and row.get("admitted_at") is not None
            and in_window(row["admitted_at"], window)]


def _mean_ms(waits):
    return 1e3 * statistics.fmean(waits) if waits else None


def compile_split(run):
    """(mean ms of the waits that overlap a host compile, their count,
    mean ms of the rest, their count) over the traced part."""
    red = scopes.for_run(run)
    if red is None or run.trace_window is None:
        return None
    t0 = run.trace_window[0]
    stalls = [(t0 + a, t0 + b) for a, b in red["compiles"]]
    hit, clear = [], []
    for s, a in _admitted(run, run.trace_window):
        (hit if any(lo < a and s < hi for lo, hi in stalls)
         else clear).append(a - s)
    return _mean_ms(hit), len(hit), _mean_ms(clear), len(clear)


def read(run):
    waits = [a - s for s, a in _admitted(run, run.window)]
    if not waits:
        return None
    print(f"queue wait over {len(waits)} requests: median "
          f"{1e3 * statistics.median(waits)} ms, mean {_mean_ms(waits)} ms; "
          f"traced part (behind a compile mean, n; the rest mean, n): "
          f"{compile_split(run)}", flush=True)
    return 1e3 * statistics.median(waits)
