"""Share of the traced window in which no operation ran on the device
while the host was inside ``engine.fetch`` (%), averaged over the cell's
chips: the idle time the step's one device-to-host fetch accounts for
(``bench/scopes.py``).  None where the engine records no spans."""
from bench import scopes


def read(run):
    red = scopes.engine_spans(run)
    if red is None or not red["window_s"]:
        return None
    return 100.0 * red["idle_fetch_s"] / red["window_s"]
