"""The routed-expert sublayer's device time in a profiler trace.

``bench/scopes.py`` gives each op to the innermost of its fixed scope
names; the MoE sublayer's own scopes, nested inside ``ffn``, are not among
them: ``moe_route`` (router, top-k, sort and dispatch), ``moe_experts``
(the expert matmuls) and ``moe_combine``.  This module reads the same
``.xplane.pb`` through ``scopes.read_planes`` and sums, over the
``bench.trace`` window, the self time of each op of the decode
(``jit_serve_step``) and prefill programs whose name stack holds one of
them, by scope, with the programs' execution counts.  The window and the
programs counted are those ``scopes.reduce`` counts.

The TPU compiler emits the grouped expert matmul (``lax.ragged_dot``) as
ops named ``ragged-dot-*`` that carry no name stack; the program has no
other ragged dot, so those ops are the experts' (``op_scope``).

A trace of a program without these scopes reads no MoE time; the readers
then report nothing.
"""
from __future__ import annotations

import bisect
import collections
import functools
import json
import os
import sys
import traceback
from typing import Dict, Optional

from bench import scopes, trace

MOE_SCOPES = ("moe_route", "moe_experts", "moe_combine")
RAGGED_DOT = "ragged-dot"


def moe_scope(tf_op: str) -> Optional[str]:
    """The innermost ``moe_*`` scope in a ``tf_op`` name stack (the first
    of fused locations), else None."""
    for part in reversed(tf_op.split(";", 1)[0].split("/")):
        part = part.split(":", 1)[0]
        if part in MOE_SCOPES:
            return part
    return None


def op_scope(name: str, tf_op: str) -> Optional[str]:
    """The ``moe_*`` scope of a device op: ``moe_scope`` of its name
    stack, else ``moe_experts`` for the compiler's ragged-dot ops."""
    scope = moe_scope(tf_op)
    if scope is None and (name.startswith(RAGGED_DOT)
                          or tf_op.startswith(RAGGED_DOT)):
        return "moe_experts"
    return scope


def reduce(path: str) -> dict:
    """{"decode_n", "prefill_n": executions in the window, "decode_s",
    "prefill_s": {scope: device seconds of self time}}."""
    planes = scopes.read_planes(path)
    host = planes["host"]
    spans = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    if not spans or not planes["devices"]:
        raise ValueError(f"{path}: no window span or no TPU device plane")
    w0, w1 = spans[0]
    dev = planes["devices"][sorted(planes["devices"])[0]]
    lo, hi = (w + trace._clock_offset(dev, host) for w in (w0, w1))
    mods = sorted((s, e, scopes.program(n)) for n, s, e in dev["modules"])
    starts = [s for s, _, _ in mods]
    runs = collections.Counter()
    for s, _, m in mods:
        if lo <= s < hi:
            runs["decode" if m == scopes.DECODE else
                 "prefill" if m.startswith(scopes.PREFILL) else "other"] += 1
    split: Dict[str, Dict[str, float]] = {
        "decode": collections.defaultdict(float),
        "prefill": collections.defaultdict(float)}
    for op, self_ns in zip(dev["ops"], scopes.self_times(dev["ops"])):
        i = bisect.bisect_right(starts, op[1]) - 1
        if i < 0 or not lo <= mods[i][0] < hi or op[1] >= mods[i][1]:
            continue
        m = mods[i][2]
        kind = ("decode" if m == scopes.DECODE else
                "prefill" if m.startswith(scopes.PREFILL) else None)
        sc = op_scope(op[0], op[3])
        if kind and sc:
            split[kind][sc] += self_ns * 1e-9
    return {"decode_n": runs["decode"], "prefill_n": runs["prefill"],
            "decode_s": dict(split["decode"]),
            "prefill_s": dict(split["prefill"])}


@functools.lru_cache(maxsize=1)
def _reduce_logged(path: str, mtime_ns: int) -> dict:
    red = reduce(path)
    print("moe scopes, device s over the traced window: "
          + json.dumps(red), flush=True)
    return red


def for_run(run) -> Optional[dict]:
    """The reduction of the trace the harness wrote for ``run``, or None
    where the run was not traced, its trace cannot be read or no op of
    the step programs carries a ``moe_*`` scope."""
    if run.trace is None:
        return None
    root = os.path.dirname(run.cell.search[0])
    try:
        path = trace.find_xplane(os.path.join(root, ".bench_out", "trace",
                                              run.cell.name))
        red = _reduce_logged(path, os.stat(path).st_mtime_ns)
    except Exception:                    # a reader must not end the run
        traceback.print_exc(file=sys.stderr)
        return None
    if not (red["decode_s"] or red["prefill_s"]):
        return None
    return red


def counted(run, window) -> list:
    """The engine's MoE executions fetched inside ``window``: (kind,
    routed pairs, experts hit), from ``Engine.stats()["moe"]["steps"]``
    ([] where the program keeps no such counter)."""
    moe = (run.stats or {}).get("moe")
    if not moe or window is None:
        return []
    return [(k, p, h) for t, k, p, h in moe["steps"]
            if window[0] <= t < window[1]]
