"""Share of decode block translations served by the restrictive segment
(%): RSW hits over RSW hits plus flexible walks, summed over the engine
steps inside the window (MetricsLogger step deltas)."""


def read(run):
    hits = sum(s.get("rsw_hits") or 0 for s in run.steps)
    walks = sum(s.get("flex_walks") or 0 for s in run.steps)
    if hits + walks == 0:
        return None
    return 100.0 * hits / (hits + walks)
