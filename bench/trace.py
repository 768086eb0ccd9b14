"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Per TPU plane: ``XLA Modules`` holds one event per program execution
(``jit_<function>(<fingerprint>)``), ``XLA Ops`` one per operation.  Busy
time is the union of operation intervals inside the traced window; idle
gaps are the holes of that union, each labelled by what the host's main
thread was doing at its middle (the innermost host event there, under the
harness's own ``bench.*`` span).  Collective time is the union of the
operations whose HLO name is a collective.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace"
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum)", re.I)
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def read_planes(path: str):
    """{'devices': {plane: {'modules': [...], 'ops': [...]}}, 'host': [...]}
    with events as (name, start_ns, end_ns); ``host`` is the main thread's
    line, the one that holds the window span."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    d["modules"] = _events(line)
                elif line.name == "XLA Ops":
                    d["ops"] = _events(line)
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    host = evs
    return {"devices": devices, "host": host}


def _clock_offset(dev: Dict[str, list], host) -> float:
    """Device-minus-host clock offset: the device never starts a program
    before the host dispatched it, so the smallest gap between a
    ``PjitFunction(f)`` dispatch and the next ``jit_f`` start bounds it."""
    starts = collections.defaultdict(list)
    for name, s, _ in host:
        if name.startswith("PjitFunction(") and name.endswith(")"):
            starts["jit_" + name[len("PjitFunction("):-1]].append(s)
    for v in starts.values():
        v.sort()
    best = None
    for name, s, _ in dev["modules"]:
        hs = starts.get(module_name(name))
        if not hs:
            continue
        i = bisect.bisect_right(hs, s) - 1
        cands = [s - hs[j] for j in (i, i + 1) if 0 <= j < len(hs)]
        d = min(cands, key=abs)
        best = d if best is None or d < best else best
    return min(best, 0.0) if best is not None else 0.0


def _label(host, t: float) -> str:
    """The bench span and the innermost host event containing ``t``."""
    outer, inner, inner_len = None, None, None
    for name, s, e in host:
        if s <= t < e and name != WINDOW_SPAN:
            if name.startswith("bench.") and (outer is None):
                outer = name
            if inner_len is None or e - s < inner_len:
                inner, inner_len = name, e - s
    if inner is None:
        return "host:none"
    if outer is None or outer == inner:
        return inner
    return f"{outer}>{inner}"


def reduce(path: str, collectives: bool = False, top: int = 10) -> dict:
    """Device metrics over the ``bench.trace`` window of one trace.

    Returns window_s, busy_s (mean over devices), per-module device
    seconds and counts (device 0), collective seconds (mean over devices),
    and the breakdown lists."""
    planes = read_planes(path)
    host = planes["host"]
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on the host")
    w0, w1 = spans[0]
    devs = planes["devices"]
    if not devs:
        raise ValueError(f"{path}: no TPU device plane")
    busy, coll = [], []
    first = sorted(devs)[0]
    mod_s: Dict[str, float] = collections.defaultdict(float)
    mod_n: Dict[str, int] = collections.defaultdict(int)
    op_s: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Interval] = []
    for name in sorted(devs):
        dev = devs[name]
        off = _clock_offset(dev, host)
        lo, hi = w0 + off, w1 + off
        ops = clip(union([(s, e) for _, s, e in dev["ops"]]), lo, hi)
        busy.append(total(ops) * 1e-9)
        if collectives:
            c = [(s, e) for n, s, e in dev["ops"]
                 if COLLECTIVE.match(op_name(n))]
            coll.append(total(clip(union(c), lo, hi)) * 1e-9)
        if name != first:
            continue
        mods = sorted((s, e, module_name(n)) for n, s, e in dev["modules"])
        for s, e, m in mods:
            if s >= lo and s < hi:
                mod_s[m] += (min(e, hi) - s) * 1e-9
                mod_n[m] += 1
        starts = [s for s, _, _ in mods]
        for n, s, e in dev["ops"]:
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            m = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op_s[f"{m}/{op_name(n)}"] += (min(e, hi) - max(s, lo)) * 1e-9
        edges = [lo] + [x for iv in ops for x in iv] + [hi]
        gaps = [(edges[i] - off, edges[i + 1] - off)
                for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devs),
        "module_s": dict(mod_s),
        "module_n": dict(mod_n),
        "collective_s": (sum(coll) / len(coll)) if collectives else None,
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_label(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle],
    }


def module_time(red: dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and executions of the modules matching ``pattern``."""
    rx = re.compile(pattern)
    s = sum(v for k, v in red["module_s"].items() if rx.search(k))
    n = sum(v for k, v in red["module_n"].items() if rx.search(k))
    return s, n


def load_reduced(trace_dir: Optional[str], **kw) -> Optional[dict]:
    if trace_dir is None:
        return None
    return reduce(find_xplane(trace_dir), **kw)
