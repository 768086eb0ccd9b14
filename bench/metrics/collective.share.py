"""Share of the device's busy time spent in collective operations (%):
the union of all-reduce / all-gather / reduce-scatter / permute / all-to-all
op intervals over the busy time, averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or not t.get("collective_s") or not t["busy_s"]:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
