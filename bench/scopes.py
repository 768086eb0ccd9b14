"""Named scopes and engine phases in a profiler trace (``.xplane.pb``).

``bench/trace.py`` reads a trace through ``jax.profiler.ProfileData``,
which does not expose the stats of an event's metadata.  Among them the
``tf_op`` stat of each device op holds the JAX name stack of the code it
came from, for example ``jit(serve_step)/while/body/closed_call/kv_pool/
dynamic_slice``.  This module parses the XSpace protobuf itself, with
message classes built at run time from the few fields it reads (no
TensorFlow), and reduces over the ``bench.trace`` window:

* the decode program (``jit_serve_step``) and the two prefill programs
  split by the innermost named scope of each op, in self time (a
  ``while`` encloses its body's ops), ``unscoped`` for ops with none;
  ``_scatter_delta``'s device time;
* the host main thread's ``engine.*`` spans: host time per engine step
  outside ``engine.fetch`` and outside compiles, and device-idle time
  while the host is inside ``engine.fetch``; the compiles on the host;
* device-idle time by engine phase (the innermost ``engine.*`` span over
  each idle interval), and the longest gaps labelled by the outer
  ``bench.*`` span, the innermost ``engine.*`` span and the innermost host
  event at their middle.

The window and the programs counted are ``bench/trace.py``'s; host and
device are put on one clock by ``clock_offset``, which pairs the decode
program with its own dispatches.
"""
from __future__ import annotations

import bisect
import collections
import functools
import json
import os
import statistics
import sys
import traceback
from typing import Dict, List, Optional, Tuple

from bench import trace

DECODE = "jit_serve_step"
DISPATCH = "PjitFunction(serve_step)"
SCATTER = "jit__scatter_delta"
SCOPES = ("translate", "kv_pool", "attn", "ffn", "lm_head", "sample")
# the prefill programs (names start so; ``_sharded`` on a mesh)
PREFILL = ("jit_prefill_step", "jit_prefix_prefill_step", "jit_prefix_step")
PREFILL_SCOPES = ("attn", "ffn", "lm_head", "sample", "kv_install",
                  "prefix_read")
UNSCOPED = "unscoped"
STEP, FETCH = "engine.step", "engine.fetch"
# the host events of obtaining an executable (tracing, lowering, compiling
# or loading it from the persistent cache): a program the warm-up missed
# holds its engine step for seconds while the host compiles
COMPILE = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
           "backend_compile_and_load")
# the client's spans in which the engine has nothing to do: waiting for
# the next request to fall due, submitting it
CLIENT_IDLE = ("bench.wait", "bench.submit")
_KNOWN = frozenset(SCOPES + PREFILL_SCOPES)


# ------------------------------------------------------------- protobuf

@functools.lru_cache(maxsize=1)
def _space_class():
    """The XSpace message class, for the fields this module reads (the
    field numbers of ``tsl/profiler/protobuf/xplane.proto``)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(container, name, *fields):
        m = container.add(name=name)
        for fname, num, typ, tname in fields:
            rep = fname.endswith("[]")
            m.field.add(name=fname.rstrip("[]"), number=num, type=typ,
                        type_name=tname or None,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)
        return m

    I64, U64, STR, MSG = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    T = ".bench_xplane."
    msg(f.message_type, "XSpace", ("planes[]", 1, MSG, T + "XPlane"))
    plane = msg(f.message_type, "XPlane",
                ("name", 2, STR, ""), ("lines[]", 3, MSG, T + "XLine"),
                ("event_metadata[]", 4, MSG,
                 T + "XPlane.EventMetadataEntry"),
                ("stat_metadata[]", 5, MSG, T + "XPlane.StatMetadataEntry"))
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = msg(plane.nested_type, entry, ("key", 1, I64, ""),
                ("value", 2, MSG, T + value))
        e.options.map_entry = True
    msg(f.message_type, "XLine", ("name", 2, STR, ""),
        ("timestamp_ns", 3, I64, ""), ("events[]", 4, MSG, T + "XEvent"))
    msg(f.message_type, "XEvent", ("metadata_id", 1, I64, ""),
        ("offset_ps", 2, I64, ""), ("duration_ps", 3, I64, ""))
    stat = msg(f.message_type, "XStat", ("metadata_id", 1, I64, ""),
               ("str_value", 5, STR, ""), ("ref_value", 7, U64, ""))
    stat.oneof_decl.add(name="value")        # the value is one of these
    for fld in stat.field[1:]:
        fld.oneof_index = 0
    msg(f.message_type, "XEventMetadata", ("name", 2, STR, ""),
        ("stats[]", 5, MSG, T + "XStat"))
    msg(f.message_type, "XStatMetadata", ("name", 2, STR, ""))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _tf_op(md, stat_names: Dict[int, str]) -> str:
    for st in md.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return (stat_names.get(st.ref_value, "")
                    if st.WhichOneof("value") == "ref_value"
                    else st.str_value)
    return ""


def read_planes(path: str):
    """``bench/trace.py``'s ``read_planes`` with each device op's
    ``tf_op``: {'devices': {plane: {'modules': [(name, s, e)], 'ops':
    [(name, s, e, tf_op)]}}, 'host': [(name, s, e)]}, times in ns; ``host``
    is the main thread's line, the one that holds the window span."""
    space = _space_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in space.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        d = {"modules": [], "ops": []}
        for line in plane.lines:
            if is_dev and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            t0 = line.timestamp_ns
            evs = []
            for e in line.events:        # whole ns, as ProfileData has them
                s = t0 + e.offset_ps // 1000
                evs.append((e.metadata_id, float(s),
                            float(s + e.duration_ps // 1000)))
            if not is_dev:
                if any(names.get(m) == trace.WINDOW_SPAN for m, _, _ in evs):
                    host = [(names.get(m, ""), s, e) for m, s, e in evs]
            elif line.name == "XLA Modules":
                d["modules"] = [(names.get(m, ""), s, e) for m, s, e in evs]
            else:
                ops = {m: (names.get(m, ""),
                           _tf_op(plane.event_metadata[m], stat_names))
                       for m in {m for m, _, _ in evs}}
                d["ops"] = [(ops[m][0], s, e, ops[m][1]) for m, s, e in evs]
        if is_dev:
            devices[plane.name] = d
    return {"devices": devices, "host": host}


# ------------------------------------------------------------ reduction

def program(event_name: str) -> str:
    """A device module's program: ``DECODE`` for every decode variant
    (``jit_serve_step``, ``jit_serve_step_sharded``), as
    ``decode.step_ms`` counts them, else ``trace.module_name``."""
    m = trace.module_name(event_name)
    return DECODE if m.startswith(DECODE) else m


def scope_of(tf_op: str) -> str:
    """The innermost of the program's named scopes in a ``tf_op`` name
    stack (XLA joins the names of fused locations with ``;``: the first
    is read), else ``unscoped``."""
    path = tf_op.split(";", 1)[0]
    for part in reversed(path.split("/")):
        part = part.split(":", 1)[0]
        if part in _KNOWN:
            return part
    return UNSCOPED


def self_times(ops) -> List[float]:
    """Each op's duration less the part of it that the ops nested in it
    cover (a ``while`` or ``conditional`` event encloses its body's ops
    on the same line); ``ops`` are (name, start, end, ...) tuples."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    out = [ops[i][2] - ops[i][1] for i in range(len(ops))]
    stack: List[Tuple[float, int]] = []          # (end, index) of parents
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return out


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(host, t: float, prefix: str = "") -> Optional[str]:
    best, best_len = None, None
    for name, s, e in host:
        if s <= t < e and name != trace.WINDOW_SPAN \
                and name.startswith(prefix) \
                and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def label(host, t: float) -> str:
    """``<bench span>><engine span>><innermost event>`` at host time
    ``t``: the outermost ``bench.*`` span, the innermost ``engine.*``
    span and the innermost host event there, repeats left out."""
    outer = max(((e - s, n) for n, s, e in host
                 if s <= t < e and n.startswith("bench.")
                 and n != trace.WINDOW_SPAN), default=(0, None))[1]
    parts = [outer, _innermost(host, t, "engine."), _innermost(host, t)]
    out: List[str] = []
    for p in parts:
        if p and (not out or out[-1] != p):
            out.append(p)
    return ">".join(out) or "host:none"


def phase_segments(host) -> List[Tuple[float, float, str]]:
    """The host main thread cut into disjoint (start, end, phase)
    segments: the innermost ``engine.*`` span there, else the outermost
    ``bench.*`` span (the window aside); none where neither is open."""
    spans = [(s, e, n) for n, s, e in host
             if n.startswith(("engine.", "bench.")) and n != trace.WINDOW_SPAN]
    events = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    active: Dict[int, Tuple[float, float, str]] = {}
    out: List[Tuple[float, float, str]] = []
    for k, (t, is_start, i) in enumerate(events):
        if is_start:
            active[i] = spans[i]
        else:
            active.pop(i, None)
        nxt = events[k + 1][0] if k + 1 < len(events) else t
        if nxt > t and active:
            eng = [sp for sp in active.values()
                   if sp[2].startswith("engine.")]
            pick = (max(eng)[2] if eng else min(active.values())[2])
            out.append((t, nxt, pick))
    return out


def idle_by_phase(gaps, segments) -> Dict[str, float]:
    """Seconds of each idle gap (host clock, sorted, disjoint) spent in
    each phase of ``phase_segments``; ``host:none`` outside them."""
    by: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for s, e in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, phase = segments[k]
            part = min(b, e) - max(a, s)
            if part > 0:
                by[phase] += part * 1e-9
                covered += part
            k += 1
        if e - s > covered:
            by["host:none"] += (e - s - covered) * 1e-9
    return dict(by)


def clock_offset(dev, host, near_ns: float = 5e6) -> float:
    """Device-minus-host clock offset, as ``bench/trace.py``'s
    ``_clock_offset`` defines it (a device never starts a program before
    the host dispatched it), from the decode program alone: the smallest
    gap between a ``jit_serve_step`` start and the nearest
    ``PjitFunction(serve_step)`` dispatch, among pairs closer than
    ``near_ns``.  ``_clock_offset`` takes the smallest over every
    program and every nearest dispatch, so a program that waited behind
    a compile or a long prefill pairs with the next dispatch instead and
    sets the offset tens to hundreds of ms off; the step's own program,
    fetched every step, starts within a millisecond of its dispatch.
    Without such a pair it falls back to ``_clock_offset``."""
    disp = sorted({s for n, s, _ in host if n == DISPATCH})
    best = None
    for name, s, _ in dev["modules"]:
        if trace.module_name(name) != DECODE:
            continue
        i = bisect.bisect_right(disp, s) - 1
        for j in (i, i + 1):
            if 0 <= j < len(disp) and abs(s - disp[j]) < near_ns:
                d = s - disp[j]
                best = d if best is None or d < best else best
    if best is None:
        return trace._clock_offset(dev, host)
    return min(best, 0.0)


def reduce(path: str, top: int = 10) -> dict:
    """The scope split, engine-phase and idle readings of one trace."""
    planes = read_planes(path)
    host = planes["host"]
    spans = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {trace.WINDOW_SPAN!r} span on the host")
    if not planes["devices"]:
        raise ValueError(f"{path}: no TPU device plane")
    w0, w1 = spans[0]
    fetch = trace.union([(s, e) for n, s, e in host if n == FETCH])
    compiles = trace.union([(s, e) for n, s, e in host if n in COMPILE])
    split: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    runs: Dict[str, int] = collections.defaultdict(int)
    scatter_s, idle_fetch, gaps = 0.0, [], []
    first = sorted(planes["devices"])[0]
    for name in sorted(planes["devices"]):
        dev = planes["devices"][name]
        off = clock_offset(dev, host)
        lo, hi = w0 + off, w1 + off
        busy = trace.clip(trace.union([(s, e) for _, s, e, _ in dev["ops"]]),
                          lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_fetch.append(trace.total(intersect(
            idle, trace.clip([(s + off, e + off) for s, e in fetch],
                             lo, hi))) * 1e-9)
        if name != first:
            continue
        gaps = [(s - off, e - off) for s, e in idle]
        # the programs ``trace.reduce`` counts, so that each split adds
        # up to that program's time (``decode.step_ms`` for the decode)
        lo, hi = (w + trace._clock_offset(dev, host) for w in (w0, w1))
        mods = sorted((s, e, program(n)) for n, s, e in dev["modules"])
        starts = [s for s, _, _ in mods]
        for s, e, m in mods:
            if lo <= s < hi:
                runs[m] += 1
                if m == SCATTER:
                    scatter_s += (min(e, hi) - s) * 1e-9
        for op, self_ns in zip(dev["ops"], self_times(dev["ops"])):
            i = bisect.bisect_right(starts, op[1]) - 1
            if (i >= 0 and mods[i][2].startswith((DECODE,) + PREFILL)
                    and lo <= mods[i][0] < hi and op[1] < mods[i][1]):
                split[mods[i][2]][scope_of(op[3])] += self_ns * 1e-9
    steps = [(s, e) for n, s, e in host if n == STEP and w0 <= s and e <= w1]
    not_host = trace.union(fetch + compiles)
    host_step_s = [((e - s) - trace.total(trace.clip(not_host, s, e)))
                   * 1e-9 for s, e in steps]
    idle_by = idle_by_phase(gaps, phase_segments(host))
    away = sum(v for k, v in idle_by.items() if k in CLIENT_IDLE)
    engine = sum(v for k, v in idle_by.items() if k.startswith("engine."))
    rest = sum(idle_by.values()) - away
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "decode_n": runs[DECODE],
        "split_s": dict(split[DECODE]),
        "scoped": any(k != UNSCOPED for k in split[DECODE]),
        "prefill_n": {m: n for m, n in runs.items() if m.startswith(PREFILL)},
        "prefill_split_s": {m: dict(v) for m, v in split.items()
                            if m.startswith(PREFILL)},
        "scatter_s": scatter_s,
        "steps": len(steps),
        "host_step_s": host_step_s,
        "compile_s": trace.total(trace.clip(compiles, w0, w1)) * 1e-9,
        # on the window's clock: seconds from the ``bench.trace`` start
        "compiles": [((s - w0) * 1e-9, (e - w0) * 1e-9)
                     for s, e in trace.clip(compiles, w0, w1)],
        "idle_fetch_s": sum(idle_fetch) / len(idle_fetch),
        "idle_by_phase_s": idle_by,
        "idle_engine_share": engine / rest if rest > 0 else None,
        "idle_gaps": [[label(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
    }


def per_run_ms(split_s: Dict[str, float], n: int, scopes) -> dict:
    """A program's split as ms per execution, ``unscoped`` and the sum
    included."""
    out = {k: 1e3 * split_s.get(k, 0.0) / (n or 1)
           for k in scopes + (UNSCOPED,)}
    out["sum"] = sum(out.values())
    return out


def summary(red: dict) -> str:
    """Four lines for a traced run's log: host time per engine step
    outside the fetch and compiles, the decode step and each prefill
    program split by scope (ms per execution), and device-idle
    seconds by engine phase with the share of the idle time outside
    ``CLIENT_IDLE`` that an engine phase holds."""
    split = per_run_ms(red["split_s"], red["decode_n"], SCOPES)
    prefill = {m: dict(per_run_ms(red["prefill_split_s"].get(m, {}), n,
                                  PREFILL_SCOPES), runs=n)
               for m, n in red["prefill_n"].items()}
    idle = sorted(red["idle_by_phase_s"].items(), key=lambda kv: -kv[1])
    host = [1e3 * v for v in red["host_step_s"]]
    return (f"engine host ms per step over {len(host)} steps: median "
            f"{statistics.median(host) if host else None}, mean "
            f"{statistics.fmean(host) if host else None}, max "
            f"{max(host, default=None)}; compiles on the host "
            f"{red['compile_s']} s\n"
            "decode step by scope, ms per step over "
            f"{red['decode_n']} steps: {json.dumps(split)}\n"
            f"prefill programs by scope, ms per execution: "
            f"{json.dumps(prefill)}\n"
            f"device idle by engine phase, s: {json.dumps(dict(idle))}; "
            f"engine share {red['idle_engine_share']}; "
            f"longest gaps: {json.dumps(red['idle_gaps'])}")


@functools.lru_cache(maxsize=1)
def _reduce_logged(path: str, mtime_ns: int) -> dict:
    red = reduce(path)
    print(summary(red), flush=True)
    return red


def for_run(run) -> Optional[dict]:
    """The reduction of the trace the harness wrote for ``run``
    (``<root>/.bench_out/trace/<cell>``), or None where the run was not
    traced or its trace cannot be read.  Reduced once per trace."""
    if run.trace is None:
        return None
    root = os.path.dirname(run.cell.search[0])
    try:
        path = trace.find_xplane(os.path.join(root, ".bench_out", "trace",
                                              run.cell.name))
        return _reduce_logged(path, os.stat(path).st_mtime_ns)
    except Exception:                    # a reader must not end the run
        traceback.print_exc(file=sys.stderr)
        return None


def scoped(run) -> Optional[dict]:
    """``for_run``, where the decode program carries named scopes and
    ran in the window: a program compiled without them reads None."""
    red = for_run(run)
    if red is None or not red["decode_n"] or not red["scoped"]:
        return None
    return red


def engine_spans(run) -> Optional[dict]:
    """``for_run``, where the host recorded engine step spans."""
    red = for_run(run)
    if red is None or not red["steps"]:
        return None
    return red
