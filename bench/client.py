"""The client: sends a mix's requests through ``Engine.submit`` / ``poll``
when they are due, and records when each token came back.

One thread does both: it submits every request that is due, then runs one
engine step (``poll``), stamps the tokens that step returned with the
time, and hands finished requests back to the generator for the follow-up
they cause.  Latency is measured from when a request was due, not from
when it was sent, so a request that waited behind a long step is charged
the wait.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.roofline import Dims, chain_ids, decode_step_need


@dataclasses.dataclass
class Rec:
    rid: int
    due: float
    sent: float
    prompt: np.ndarray
    max_new: int
    item: object
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    reason: Optional[str] = None
    blocks: Optional[list] = None


@dataclasses.dataclass
class Poll:
    """One engine step as the client saw it."""
    t0: float
    t1: float
    decode_flops: float = 0.0
    decode_bytes: float = 0.0
    decode_rows: int = 0


class Client:
    def __init__(self, engine, request_cls, traffic, t0: float,
                 dims: Dims, clock: Callable[[], float] = time.perf_counter,
                 span=None):
        self.engine = engine
        self.Request = request_cls
        self.traffic = traffic
        self.t0 = t0
        self.dims = dims
        self.clock = clock
        self.span = span or (lambda name: contextlib.nullcontext())
        self.recs: Dict[int, Rec] = {}
        self.polls: List[Poll] = []
        self.ids = itertools.count()
        self.heap: list = []
        self.seq = itertools.count()
        for it in traffic.start():
            self._push(it)

    def _push(self, item) -> None:
        heapq.heappush(self.heap, (self.t0 + item.due, next(self.seq), item))

    def _submit_due(self, now: float) -> None:
        while self.heap and self.heap[0][0] <= now:
            due, _, item = heapq.heappop(self.heap)
            rid = next(self.ids)
            self.engine.submit(self.Request(seq_id=rid, prompt=item.prompt,
                                            max_new_tokens=item.max_new))
            self.recs[rid] = Rec(rid=rid, due=due, sent=self.clock(),
                                 prompt=item.prompt, max_new=item.max_new,
                                 item=item)

    def _decode_need(self, rec: Rec, k: int):
        """Context and block ids of the step that produced token ``k``."""
        bs = self.dims.block
        if rec.blocks is None:
            rec.blocks = chain_ids(rec.prompt.tolist(), bs)
        P = len(rec.prompt)
        ctx = P + k - 1
        n = -(-ctx // bs)
        own = [(rec.rid, j) for j in range(len(rec.blocks), n)]
        return ctx, rec.blocks[:n] + own

    def step(self) -> bool:
        """Submit what is due and run one engine step (or wait for the next
        due request).  Returns False when nothing is left to do."""
        now = self.clock()
        with self.span("bench.submit"):
            self._submit_due(now)
        if not self.engine.has_unfinished():
            if not self.heap:
                return False
            wait = min(self.heap[0][0] - self.clock(), 0.05)
            if wait > 0:
                with self.span("bench.wait"):
                    time.sleep(wait)
            return True
        t0 = self.clock()
        with self.span("bench.poll"):
            outs = self.engine.poll()
        t1 = self.clock()
        poll = Poll(t0=t0, t1=t1)
        rows = []
        for out in outs:
            rec = self.recs.get(out.seq_id)
            if rec is None:          # a warm-up request, not the mix's
                continue
            for tok in out.new_token_ids:
                k = len(rec.tokens)
                if k:
                    rows.append(self._decode_need(rec, k))
                rec.tokens.append(int(tok))
                rec.times.append(t1)
            if out.finished and rec.finished is None:
                rec.finished = t1
                rec.reason = out.finish_reason
                for it in self.traffic.done(rec.item, t1 - self.t0,
                                            rec.tokens):
                    self._push(it)
        if rows:
            poll.decode_flops, poll.decode_bytes = decode_step_need(
                self.dims, rows)
            poll.decode_rows = len(rows)
        self.polls.append(poll)
        return True

    def run_until(self, t_end: float, hook=None) -> None:
        while self.clock() < t_end:
            if hook is not None:
                hook(self.clock())
            if not self.step():
                break

    def drain_first_tokens(self, lo: float, hi: float,
                           grace_s: float) -> None:
        """Keep serving until every request due in [lo, hi) has its first
        token, or ``grace_s`` past ``hi``."""
        limit = hi + grace_s
        while self.clock() < limit:
            if not any(not r.tokens for r in self.recs.values()
                       if lo <= r.due < hi):
                return
            if not self.step():
                return

    # ----------------------------------------------------------- readings
    def due_in(self, lo: float, hi: float) -> List[Rec]:
        return [r for r in self.recs.values() if lo <= r.due < hi]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100])."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(0, min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])


def end_to_end(client: Client, lo: float, hi: float, missing_s: float):
    """Client-side readings over the window [lo, hi)."""
    due = client.due_in(lo, hi)
    ttft = [(r.times[0] - r.due) if r.tokens else missing_s for r in due]
    gaps = []
    out_tokens = 0
    for r in client.recs.values():
        for i, t in enumerate(r.times):
            if lo <= t < hi:
                out_tokens += 1
                if i > 0:
                    gaps.append(t - r.times[i - 1])
    late = [r.sent - r.due for r in due]
    return {
        "due": len(due),
        "failed": sum(1 for r in due if not r.tokens),
        "ttft_s": ttft,
        "gaps_s": gaps,
        "output_tokens": out_tokens,
        "late_s": late,
    }
