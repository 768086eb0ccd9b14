"""The benchmark's parts on their own: the contract's file, the roofline
counts, the traffic generators, the warm-up plan and the readers."""
import json
import os
import re

import numpy as np
import pytest

from bench import roofline, spec, warmup
from bench.traffic import Pool, bursty_arrivals, lognormal

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_file_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert NAME.match(c["name"])
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in cells
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells), (m["name"], w)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in bench["workloads"]:
        reported = [m for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(w["name"] in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_cell_finds_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        spec.load_module(cell.search, "mixes", cell.mix["kind"])
        spec.load_module(cell.search, "configs", cell.config["reference"])
        for m in cell.per_layer:
            assert hasattr(spec.load_module(cell.search, "metrics",
                                            m["name"]), "read")
        assert cell.params["check"]["gap_limit"] > 0
        if w["traffic"] == "sessions":
            assert cell.params["rate"] > 0


def test_config_reduced_keys_are_not_widths(bench):
    widths = re.compile(r"(^hidden_size$|intermediate|latent|state|proj|"
                        r"_dim$|_rank$|head_dim|expan|experts_per_tok)")
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert not widths.search(k), k
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]


def test_unknown_device_kind_raises():
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def _dims():
    return roofline.Dims(d=64, heads=4, kv_heads=2, head_dim=16, ff=128,
                         layers=2, vocab=256, qkv_bias=False, el_bytes=2,
                         block=8)


def test_decode_count_ignores_the_gather_width():
    dims = _dims()
    ctx = [13, 40, 7]
    tables = [[f"r{i}b{j}" for j in range(-(-c // 8))]
              for i, c in enumerate(ctx)]
    bounded = list(zip(ctx, tables))
    full = [(c, t + [-1] * (16 - len(t))) for c, t in bounded]
    assert roofline.decode_step_need(dims, full) == \
        roofline.decode_step_need(dims, bounded)


def test_shared_prefix_block_is_counted_once():
    dims = _dims()
    shared = ["sys0", "sys1"]
    a = (20, shared + ["a2"])
    b = (20, shared + ["b2"])
    apart = roofline.decode_step_need(
        dims, [a, (20, ["x0", "x1", "b2"])])[1]
    together = roofline.decode_step_need(dims, [a, b])[1]
    assert apart - together == 2 * 8 * dims.kv_bytes_per_token
    one = roofline.decode_step_need(dims, [a])
    assert one[1] == (roofline.decode_weight_bytes(dims, 1)
                      + 21 * dims.kv_bytes_per_token)


def test_prefill_flops_match_token_by_token():
    dims = _dims()
    whole = roofline.prefill_flops(dims, 16, 40)
    parts = sum(roofline.token_flops(dims, p + 1, logits=(p == 39))
                for p in range(16, 40))
    assert whole == pytest.approx(parts)


def test_pool_gives_every_seed_the_same_sizes():
    ppf = lognormal(96, 0.8, 16, 512)
    a = [Pool(np.random.default_rng(1), ppf, 32).draw() for _ in range(1)]
    draws = {}
    for seed in (1, 2, 2 ** 33 + 5):
        p = Pool(np.random.default_rng(seed), ppf, 32)
        draws[seed] = [p.draw() for _ in range(64)]
    assert sorted(draws[1]) == sorted(draws[2]) == sorted(draws[2 ** 33 + 5])
    assert draws[1] != draws[2]
    assert a


def test_bursty_arrivals_offer_every_seed_the_same_load():
    per_window = []
    for seed in (1, 2, 3, 2 ** 40 + 1):
        t = bursty_arrivals(np.random.default_rng(seed), 1.3, 3.0, 2.0, 12.0,
                            48.0)
        assert all(b >= a for a, b in zip(t, t[1:]))
        per_window.append(sum(1 for x in t if 12.0 <= x < 48.0))
    assert len(set(per_window)) == 1
    assert per_window[0] == pytest.approx(36 * 1.3 * 16 / 12, abs=2)
    a = bursty_arrivals(np.random.default_rng(1), 1.3, 3.0, 2.0, 12.0, 48.0)
    b = bursty_arrivals(np.random.default_rng(2), 1.3, 3.0, 2.0, 12.0, 48.0)
    assert a != b


def test_sessions_are_seeded_and_fit_the_context():
    cell = spec.load_cell("granite-8b.sessions")
    gen = spec.load_module(cell.search, "mixes", cell.mix["kind"])
    mk = lambda s: gen.Traffic(cell.mix, cell.config, cell.params, s, 40.0)
    a, b = mk(7).start(), mk(7).start()
    assert [(i.due, len(i.prompt)) for i in a] == \
        [(i.due, len(i.prompt)) for i in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    for it in a:
        assert len(it.prompt) % 64 == 0
        assert len(it.prompt) + it.max_new <= cell.config["max_seq_len"]
    t = mk(7)
    first = t.start()[0]
    nxt = t.done(first, first.due + 1.0, [5] * first.max_new)
    if nxt:
        p = nxt[0].prompt
        assert (p[:len(first.prompt)] == first.prompt).all()
        assert nxt[0].due > first.due + 1.0


def test_warmup_plan_hits_the_shapes_it_names():
    bs, budget = 64, 4096
    shapes = warmup.plan({"prefill": (1088, 4032), "prefix_tail": (64, 1984),
                          "prefix_end": (1088, 4032)}, bs, budget, 16,
                         {"prefill": 4, "prefix": 4})
    pow2 = lambda n: 1 << max(0, n - 1).bit_length()
    seen = set()
    for kind, n, sb, nb, length, tail in shapes:
        assert n * tail <= budget
        assert pow2(tail // bs) == sb
        assert length % bs == 0 and tail % bs == 0
        if kind == "prefix":
            assert pow2(length // bs) == nb and length - tail >= bs
        key = (kind, pow2(n), sb, nb)
        assert key not in seen
        seen.add(key)
    groups = warmup.groups(shapes, bs, 256, np.random.default_rng(0))
    assert sum(len(g) for g in groups) >= sum(s[1] for s in shapes)
