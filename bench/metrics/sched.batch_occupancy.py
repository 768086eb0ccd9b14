"""Mean live sequences per engine step over ``max_batch`` (%), from the
``live`` gauge of the MetricsLogger's step events inside the window."""


def read(run):
    live = [s["live"] for s in run.steps if s.get("live") is not None]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / run.max_batch
