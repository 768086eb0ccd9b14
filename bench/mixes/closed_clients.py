"""Closed-loop clients with unique prompts (offline bulk jobs).

``clients`` clients (a number, or ``"max_batch"`` for the configuration's
batch) each send their next request as soon as the last one finishes.
Every prompt is fresh random tokens, so no prefix is shared.  Prompt and
answer lengths are clipped lognormals; a prompt is rounded up to whole KV
blocks.  Answers are greedy, ``max_new_tokens`` long.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.traffic import Item, Pool, lognormal, round_up, tokens


class Traffic:
    open_loop = False

    def __init__(self, p: dict, config: dict, cell: dict, seed: int,
                 horizon_s: float):
        self.rng = np.random.default_rng(seed)
        self.p = p
        self.vocab = int(config["vocab_size"])
        self.block = int(config["kv_block_size"])
        self.max_seq = int(config["max_seq_len"])
        self.warmup_s = float(p["warmup_s"])
        n = p["clients"]
        self.clients = int(config["max_batch"] if n == "max_batch" else n)
        pr, an = p["prompt"], p["answer"]
        self.prompt = Pool(self.rng, lognormal(pr["median"], pr["sigma"],
                                               pr["min"], pr["max"]))
        self.answer = Pool(self.rng, lognormal(an["median"], an["sigma"],
                                               an["min"], an["max"]))

    def _next(self, client: int, due: float) -> Item:
        n = min(round_up(int(round(self.prompt.draw())), self.block),
                self.max_seq - self.block)
        ans = min(int(round(self.answer.draw())), self.max_seq - n)
        return Item(due=due, prompt=tokens(self.rng, n, self.vocab),
                    max_new=ans, tag=client)

    def start(self) -> List[Item]:
        return [self._next(c, 0.0) for c in range(self.clients)]

    def done(self, item: Item, t: float, served) -> List[Item]:
        return [self._next(item.tag, t)]

    def bounds(self, budget: int) -> dict:
        """Chunk-0 prompts of every length, and the rest of a prompt that
        the per-step budget split, which follows its own first chunk."""
        pr, bs = self.p["prompt"], self.block
        lo, hi = round_up(pr["min"], bs), round_up(pr["max"], bs)
        return {"prefill": (bs, min(hi, budget)),
                "prefix_tail": (bs, min(hi - bs, budget)),
                "prefix_end": (2 * bs, hi)}
