"""HLO post-processing for the roofline: trip-count-corrected FLOPs/bytes
and per-device collective traffic.

Why this exists: ``compiled.cost_analysis()`` counts a while-loop body
ONCE (verified empirically: an 80-layer scanned model reports 1/80th of
analytic FLOPs).  The roofline must therefore re-weight per-computation
costs by loop trip counts.  Collective bytes are not in cost_analysis at
all — they are summed from the HLO text, weighted by the enclosing
computation's multiplier and the op's replica group size.

Per-device moved-bytes model (ring algorithms):
    all-reduce(S)          2 * S * (g-1)/g
    all-gather(R)          R * (g-1)/g         (R = gathered result)
    reduce-scatter(R)      R * (g-1)           (R = scattered result)
    all-to-all(S)          S * (g-1)/g
    collective-permute(S)  S
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\w+\[[\d,]*\](?:\{[^}]*\})?|\((?:[^()]|\([^()]*\))*\)))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COMP_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?\).*?condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[=\{":\s]+n["\s:]*"?(\d+)')
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of 'bf16[1,2,3]' or a tuple '(f32[2], s32[])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """Top-level computation blocks.  HLO text nests braces only in
    attribute lists within a line, so a computation starts at an unindented
    ``name (args) -> type {`` line and ends at a lone ``}``."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if cur is None:
            if (line and not line[0].isspace() and line.rstrip().endswith("{")
                    and "=" not in line.split("(")[0]):
                m = _COMP_NAME_RE.match(line.strip())
                if m:
                    cur = m.group(1)
                    comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        comps[cur].append(line.strip())
    return comps


def _trip_count(while_line: str, cond_lines: List[str]) -> int:
    """Loop bound: XLA's known_trip_count backend_config, else the largest
    positive constant in the condition computation."""
    m = _TRIP_RE.search(while_line)
    if m:
        return int(m.group(1))
    consts = []
    for ln in cond_lines:
        if "compare" in ln or "constant" in ln:
            consts += [int(c) for c in _CONST_RE.findall(ln)]
    consts = [c for c in consts if c > 0]
    return max(consts) if consts else 1


def analyze_collectives(hlo: str) -> Dict:
    """Trip-count-weighted per-device collective bytes from HLO text."""
    comps = _split_computations(hlo)

    # call graph: comp -> [(child, multiplier)]
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(ln, comps.get(cond, []))
                edges[name].append((body, trips))
                edges[name].append((cond, trips))
                continue
            for cm in _CALL_RE.finditer(ln):
                edges[name].append((cm.group(1), 1))

    # multipliers via DFS from entry (last computation = ENTRY by convention;
    # find the one nobody calls)
    called = {c for outs in edges.values() for c, _ in outs}
    roots = [c for c in comps if c not in called] or list(comps)[-1:]
    mult: Dict[str, int] = defaultdict(int)

    def visit(name: str, m: int, depth=0):
        if depth > 50:
            return
        mult[name] += m
        for child, k in edges.get(name, []):
            if child in comps:
                visit(child, m * k, depth + 1)

    for r in roots:
        visit(r, 1)

    per_kind_bytes: Dict[str, float] = defaultdict(float)
    per_kind_count: Dict[str, int] = defaultdict(int)
    for name, lines in comps.items():
        m = max(mult.get(name, 1), 1)
        for ln in lines:
            cm = _COLL_RE.search(ln)
            if not cm:
                continue
            size = _shape_bytes(cm.group(1))
            kind = cm.group(2)
            g = None
            gm = _GROUPS_RE.search(ln)
            if gm:
                g = len(gm.group(1).split(","))
            else:
                gi = _GROUPS_IOTA_RE.search(ln)
                if gi:
                    g = int(gi.group(2))
            if not g or g <= 1:
                g = 2  # conservative default
            frac = (g - 1) / g
            if kind == "all-reduce":
                moved = 2 * size * frac
            elif kind == "all-gather":
                moved = size * frac
            elif kind == "reduce-scatter":
                moved = size * (g - 1)
            elif kind == "all-to-all":
                moved = size * frac
            else:  # collective-permute
                moved = size
            per_kind_bytes[kind] += moved * m
            per_kind_count[kind] += m

    return {
        "collective_bytes_per_device": sum(per_kind_bytes.values()),
        "per_kind_bytes": dict(per_kind_bytes),
        "per_kind_count": dict(per_kind_count),
        "n_computations": len(comps),
    }


_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"
                    r"((?:\w+\[[\d,]*\](?:\{[^}]*\})?|\((?:[^()]|\([^()]*\))*\)))\s*"
                    r"([\w\-]+)\(([^)]*)\)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BDIMS_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")
_WINDOW_RE = re.compile(r"window=\{[^}]*size=([\dx]+)")
_FGC_RE = re.compile(r"feature_group_count=(\d+)")


def _dims_of(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


def analyze_costs(hlo: str) -> Dict:
    """Trip-count-weighted FLOPs and HBM bytes from the post-opt HLO.

    * FLOPs: dot ops (2*result*K, K from lhs shape + contracting dims) and
      convolutions (2*result*window*Cin/groups).  Element-wise flops are
      ignored (dots dominate every assigned arch by >100x).
    * Bytes: per top-level op, operands + result sizes — post-optimization
      HLO is fusion-granular, so this approximates kernel-level HBM
      traffic the same way XLA's own bytes-accessed does.
    Each computation's contribution is multiplied by its loop/call
    multiplier (the correction cost_analysis lacks).
    """
    comps = _split_computations(hlo)

    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                trips = _trip_count(ln, comps.get(wm.group(1), []))
                edges[name].append((wm.group(2), trips))
                edges[name].append((wm.group(1), trips))
                continue
            for cm in _CALL_RE.finditer(ln):
                edges[name].append((cm.group(1), 1))

    called = {c for outs in edges.values() for c, _ in outs}
    roots = [c for c in comps if c not in called] or list(comps)[-1:]
    mult: Dict[str, int] = defaultdict(int)

    def visit(name: str, m: int, depth=0):
        if depth > 50:
            return
        mult[name] += m
        for child, k in edges.get(name, []):
            if child in comps:
                visit(child, m * k, depth + 1)

    for r in roots:
        visit(r, 1)

    total_flops = 0.0
    total_bytes = 0.0
    per_comp_flops: Dict[str, float] = defaultdict(float)
    for name, lines in comps.items():
        m = max(mult.get(name, 1), 1)
        symbols: Dict[str, str] = {}
        cflops = 0.0
        cbytes = 0.0
        for ln in lines:
            om = _OP_RE.match(ln)
            if not om:
                continue
            oname, oshape, okind, oargs = om.groups()
            symbols[oname] = oshape
            result_bytes = _shape_bytes(oshape)
            operand_bytes = 0
            for a in oargs.split(","):
                a = a.strip().lstrip("%")
                a = a.split(" ")[0]
                if a in symbols:
                    operand_bytes += _shape_bytes(symbols[a])
            # HBM-traffic ops only: on TPU the element-wise/convert/copy
            # chains fuse into their consumers, so counting them (as the
            # unfused CPU HLO would suggest) overstates traffic ~10x.
            if okind in ("fusion", "dot", "convolution", "custom-call",
                         "all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute",
                         "dynamic-update-slice", "scatter", "gather",
                         "reduce", "sort", "dynamic-slice",
                         "select-and-scatter"):
                cbytes += result_bytes + operand_bytes
            if okind == "dot":
                rdims = _dims_of(oshape) or []
                lhs = oargs.split(",")[0].strip().lstrip("%").split(" ")[0]
                ldims = _dims_of(symbols.get(lhs, "")) or []
                cd = _CDIMS_RE.search(ln)
                k = 1
                if cd and cd.group(1):
                    for d in cd.group(1).split(","):
                        di = int(d)
                        if di < len(ldims):
                            k *= ldims[di]
                n = 1
                for d in rdims:
                    n *= d
                cflops += 2.0 * n * k
            elif okind == "convolution":
                rdims = _dims_of(oshape) or []
                n = 1
                for d in rdims:
                    n *= d
                w = _WINDOW_RE.search(ln)
                win = 1
                if w:
                    for d in w.group(1).split("x"):
                        win *= int(d)
                cflops += 2.0 * n * win
        total_flops += cflops * m
        total_bytes += cbytes * m
        if cflops:
            per_comp_flops[name] = cflops * m

    top = sorted(per_comp_flops.items(), key=lambda kv: -kv[1])[:10]
    return {"flops_weighted": total_flops, "bytes_weighted": total_bytes,
            "top_computations": top}


def loop_corrected_costs(compiled, hlo: Optional[str] = None) -> Dict:
    """cost_analysis with while-loop bodies re-weighted by trip count.

    XLA attributes body costs to the entry once; we approximate the
    correction by multiplying the whole-program flops/bytes by the
    dominant loop weight when a single top-level scan dominates.  The
    robust path (used by the roofline) is analytic-per-layer x L,
    cross-checked against this.
    """
    ca = compiled.cost_analysis() or {}
    if hlo is None:
        hlo = compiled.as_text()
    comps = _split_computations(hlo)
    # find top-level while trip counts (in ENTRY or main computations)
    trips = []
    for name, lines in comps.items():
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                trips.append(_trip_count(ln, comps.get(wm.group(1), [])))
    return {
        "raw_flops": float(ca.get("flops", 0.0)),
        "raw_bytes": float(ca.get("bytes accessed", 0.0)),
        "loop_trip_counts": sorted(trips, reverse=True)[:8],
    }
