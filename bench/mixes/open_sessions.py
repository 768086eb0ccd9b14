"""Multi-turn chat sessions, open loop, with bursts.

Session starts arrive at the cell's ``rate`` (sessions/s), raised to
``burst_factor`` times that rate for one burst of ``burst_s`` seconds in
every ``cycle_s`` seconds (``traffic.bursty_arrivals``: a fixed count per
cycle, placed as Poisson arrivals given that count).  Each session picks one of ``system_prompts`` shared system prompts
of ``system_len`` tokens by Zipf(``system_zipf_s``) and runs
``1 + Geometric(turns_p)`` turns, at most ``turns_max``.  A turn's prompt is
the system prompt, the whole history (earlier messages and the answers
served to them) and a new message; it is due at the session's start, or
``think`` seconds after the previous answer's last token.  A session ends
when its next turn would not fit in ``max_seq_len``.  Message and answer
lengths are clipped lognormals; a message is lengthened to the next whole
KV block of the prompt.  Answers are greedy, ``max_new_tokens`` long.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.traffic import (Item, Pool, bursty_arrivals, exponential,
                           geometric_failures, lognormal, round_up, tokens,
                           zipf_index)


class Session:
    def __init__(self, system: int, turns: int):
        self.system = system
        self.turns = turns
        self.history: List[np.ndarray] = []
        self.done_turns = 0


class Traffic:
    open_loop = True

    def __init__(self, p: dict, config: dict, cell: dict, seed: int,
                 horizon_s: float):
        rng = np.random.default_rng(seed)
        self.rng = rng
        self.p = p
        self.vocab = int(config["vocab_size"])
        self.block = int(config["kv_block_size"])
        self.max_seq = int(config["max_seq_len"])
        self.warmup_s = float(p["warmup_s"])
        m, a, n = p["message"], p["answer"], int(p["pool"])
        self.msg = Pool(rng, lognormal(m["median"], m["sigma"], m["min"],
                                       m["max"]), n)
        self.ans = Pool(rng, lognormal(a["median"], a["sigma"], a["min"],
                                       a["max"]), n)
        self.think = Pool(rng, exponential(p["think_mean_s"]), n)
        turns = Pool(rng, geometric_failures(p["turns_p"],
                                             p["turns_max"] - 1), n // 2)
        pick = Pool(rng, zipf_index(p["system_prompts"], p["system_zipf_s"]),
                    n // 2)
        self.systems = [tokens(rng, p["system_len"], self.vocab)
                        for _ in range(p["system_prompts"])]
        starts = bursty_arrivals(rng, float(cell["rate"]), p["burst_factor"],
                                 p["burst_s"], p["cycle_s"], horizon_s)
        self.starts = [(t, Session(int(pick.draw()), 1 + int(turns.draw())))
                       for t in starts]

    def _turn(self, s: Session, due: float):
        ans = int(round(self.ans.draw()))
        hist = len(self.systems[s.system]) + sum(len(h) for h in s.history)
        n_msg = round_up(hist + int(round(self.msg.draw())), self.block) - hist
        if hist + n_msg + ans > self.max_seq:
            return None
        msg = tokens(self.rng, n_msg, self.vocab)
        s.history.append(msg)
        prompt = np.concatenate([self.systems[s.system]] + s.history)
        return Item(due=due, prompt=prompt, max_new=ans, tag=s)

    def start(self) -> List[Item]:
        out = []
        for t, s in self.starts:
            it = self._turn(s, t)
            if it is not None:
                out.append(it)
        return out

    def done(self, item: Item, t: float, served) -> List[Item]:
        s = item.tag
        s.history.append(np.asarray(served, np.int32))
        s.done_turns += 1
        if s.done_turns >= s.turns:
            return []
        it = self._turn(s, t + self.think.draw())
        return [it] if it is not None else []

    def bounds(self, budget: int) -> dict:
        """Lengths the engine can see, for the warm-up of its shapes:
        chunk-0 prompts (a system prompt not yet cached, or evicted from
        the cache under pool pressure), and chunks that
        follow a cached prefix (everything after the system prompt or an
        earlier turn's prompt, or the rest of a prompt split by the
        per-step budget)."""
        p, bs = self.p, self.block
        sys_len = int(p["system_len"])
        first_hi = round_up(sys_len + p["message"]["max"], bs)
        tail_hi = round_up(p["answer"]["max"] + p["message"]["max"], bs)
        return {"prefill": (round_up(sys_len + p["message"]["min"], bs),
                            min(self.max_seq - bs, budget)),
                "prefix_tail": (bs, min(max(tail_hi, first_hi - bs),
                                        budget)),
                "prefix_end": (sys_len + bs, self.max_seq - bs)}
