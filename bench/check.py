"""Whether what the timed path served is correct.

After the window a sample of the finished requests, drawn from the seed and
always holding the longest one, is run once through the configuration's
plain float32 reference over prompt + served tokens.  For every served
token the reference gives the gap by which that token's logit lies below
its best logit; greedy serving in exact arithmetic gives 0, and rounding
only lets a near-tie flip.  The run is correct when the widest gap over the
sample stays within the cell's limit and every token id is in the
vocabulary.  The control (``fp8``) reads the gap of the token that the
reference computed in float8 puts first, at the same positions.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(recs: Sequence, rng: np.random.Generator, n_max: int,
           tokens_min: int) -> List:
    """Finished requests: the longest (prompt + served) first, then others
    in an order from ``rng`` until ``tokens_min`` served tokens or
    ``n_max`` requests."""
    done = sorted((r for r in recs if r.finished is not None and r.tokens),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    order = [rest[i] for i in rng.permutation(len(rest))]
    out, served = [longest], len(longest.tokens)
    for r in order:
        if len(out) >= n_max or served >= tokens_min:
            break
        out.append(r)
        served += len(r.tokens)
    return out


def gaps(ref, config: dict, params, recs: Sequence,
         controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """Per served token, the float32 reference's best logit minus its logit
    of the served token (``"served"``); for each precision in ``controls``
    the same gap of the token that precision ranks first.  An id outside
    the vocabulary reads as an infinite gap."""
    vocab = int(config["vocab_size"])
    out: Dict[str, list] = {k: [] for k in ("served",) + tuple(controls)}
    for r in recs:
        served = np.asarray(r.tokens, np.int64)
        if (served < 0).any() or (served >= vocab).any():
            for k in out:
                out[k].append(np.full(len(served), np.inf))
            continue
        P = len(r.prompt)
        seq = np.concatenate([np.asarray(r.prompt, np.int64), served[:-1]])
        at = np.arange(P - 1, P - 1 + len(served))
        ref32 = ref.logits_at(config, params, seq, at, "float32")
        best = ref32.max(axis=-1)
        rows = np.arange(len(served))
        out["served"].append(best - ref32[rows, served])
        for p in controls:
            pick = ref.logits_at(config, params, seq, at, p).argmax(axis=-1)
            out[p].append(best - ref32[rows, pick])
    return {k: (np.concatenate(v) if v else np.zeros(0))
            for k, v in out.items()}
