"""A temporary benchmark root with tiny cells, for the CPU tests.

The files follow the real ones' schema at reduced width; the generators,
metric readers and the reference are found beside the harness."""
from __future__ import annotations

import json
import os

TINY = {
    "source": "test", "arch": "granite-8b", "reference": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "attention_bias": True, "tie_word_embeddings": False,
    "torch_dtype": "float32", "kv_block_size": 8, "max_batch": 4,
    "max_seq_len": 128, "mesh_shape": None, "mode": "hybrid",
}
SESSIONS = {
    "kind": "open_sessions", "warmup_s": 0.3, "burst_factor": 3,
    "burst_s": 0.2, "cycle_s": 0.7, "pool": 16, "system_prompts": 2,
    "system_len": 32, "system_zipf_s": 1.1, "turns_p": 0.34,
    "turns_max": 4,
    "message": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
    "answer": {"median": 6, "sigma": 0.5, "min": 2, "max": 10},
    "think_mean_s": 0.05, "warm_rows": {"prefill": 2, "prefix": 2},
}
BATCH = {
    "kind": "closed_clients", "warmup_s": 0.2, "clients": "max_batch",
    "prompt": {"median": 32, "sigma": 0.5, "min": 8, "max": 64},
    "answer": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "warm_rows": {"prefill": 2, "prefix": 1},
}
CHECK = {"requests": 3, "tokens": 24, "gap_limit": 1e-3}


def write_root(root: str, extra_mix=None) -> str:
    """BENCHMARK.json and the tiny cells' files under ``root``."""
    b = os.path.join(root, "bench")
    for sub in ("configs", "mixes", "cells"):
        os.makedirs(os.path.join(b, sub), exist_ok=True)

    def dump(path, obj):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)

    dump("configs/tiny.json", TINY)
    dump("mixes/tiny_sessions.json", SESSIONS)
    dump("mixes/tiny_batch.json", BATCH)
    dump("cells/tiny.tiny_sessions.json", {"rate": 6.0, "check": CHECK})
    dump("cells/tiny.tiny_batch.json", {"check": CHECK})
    cells = [("tiny.tiny_sessions", "tiny_sessions"),
             ("tiny.tiny_batch", "tiny_batch")]
    if extra_mix is not None:
        name, mix = extra_mix
        dump(f"mixes/{name}.json", mix)
        dump(f"cells/tiny.{name}.json", {"rate": 6.0, "check": CHECK})
        cells.append((f"tiny.{name}", name))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(_benchmark(cells), f)
    return root


def _benchmark(cells):
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "BENCHMARK.json")) as f:
        real = json.load(f)
    open_cells = [c for c, m in cells if m != "tiny_batch"]
    all_cells = [c for c, _ in cells]

    def remap(ms):
        out = []
        for m in ms:
            m = dict(m)
            if "workloads" in m:
                moved = m.get("moves", m["name"])
                m["workloads"] = (all_cells if moved == "output_tokens_per_s"
                                  else open_cells)
            out.append(m)
        return out

    return {**real,
            "configs": [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}],
            "workloads": [{"name": c, "config": "tiny", "traffic": m,
                           "chips": 1, "why": "test"} for c, m in cells],
            "end_to_end": remap(real["end_to_end"]),
            "per_layer": remap(real["per_layer"])}
