"""Warm-up of the engine's program shapes through its public API.

The engine pads a prefill group to a power-of-two count of rows and each
row to a power-of-two count of KV blocks; a chunk that follows a cached or
already installed prefix is padded the same way and keyed also by the
power-of-two block width of the prefix it reads.  Each such shape is one
program.  From the lengths a mix can produce (its ``bounds``) this module
lists, for every shape the mix can reach, the fewest requests that make the
engine run it, and submits them group by group to the idle engine.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _pow2_range(lo_blocks: int, hi_blocks: int) -> List[int]:
    out, b = [], _pow2(lo_blocks)
    while b <= _pow2(hi_blocks):
        out.append(b)
        b *= 2
    return out


def plan(bounds: Dict[str, Tuple[int, int]], block: int, budget: int,
         max_batch: int, rows_max: Dict[str, int]):
    """[(kind, rows, s_pad_blocks, nblk, length, tail)]: ``rows`` requests
    of ``length`` tokens each, the first ``length - tail`` of them a
    prefix already in the cache (``tail == length`` for chunk 0)."""
    out = []
    b_max = _pow2(max_batch)
    lo, hi = bounds["prefill"]
    for sb in _pow2_range(-(-lo // block), hi // block):
        length = min(max(lo, (sb // 2 + 1) * block), sb * block, hi)
        if _pow2(length // block) != sb:
            continue
        b = 1
        while b <= min(b_max, rows_max["prefill"]):
            n = 1 if b == 1 else b // 2 + 1
            if n * length <= budget and n <= max_batch:
                out.append(("prefill", n, sb, 0, length, length))
            b *= 2
    t_lo, t_hi = bounds["prefix_tail"]
    e_lo, e_hi = bounds["prefix_end"]
    for sb in _pow2_range(-(-t_lo // block), t_hi // block):
        tail = min(max(t_lo, (sb // 2 + 1) * block), sb * block, t_hi)
        if _pow2(tail // block) != sb:
            continue
        for nb in _pow2_range(-(-e_lo // block), e_hi // block):
            end = min(nb * block, e_hi)
            if _pow2(end // block) != nb or end - tail < block:
                continue
            b = 1
            while b <= min(b_max, rows_max["prefix"]):
                n = 1 if b == 1 else b // 2 + 1
                if n * tail <= budget and n <= max_batch:
                    out.append(("prefix", n, sb, nb, end, tail))
                b *= 2
    return out


def groups(shapes, block: int, vocab: int, rng: np.random.Generator):
    """Prompt groups to submit one after the other to an idle engine.
    Prefixes are cut from one primer sequence, cached first in two steps
    (its first half by a chunk-0 prefill, the rest after it)."""
    longest = max([s[4] for s in shapes if s[0] == "prefix"], default=0)
    primer = rng.integers(0, vocab, size=longest, dtype=np.int32)
    out: List[List[np.ndarray]] = []
    if longest:
        half = longest // 2 // block * block or longest
        out.append([primer[:half]])
        if half < longest:
            out.append([primer[:longest]])
    for kind, n, sb, nb, length, tail in shapes:
        rows = []
        for _ in range(n):
            own = rng.integers(0, vocab, size=tail, dtype=np.int32)
            rows.append(own if kind == "prefill" else
                        np.concatenate([primer[:length - tail], own]))
        out.append(rows)
    return out
