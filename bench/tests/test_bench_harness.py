"""CPU rehearsal of whole runs at reduced width, through ``make_engine``.

The tiny cells live in a temporary root (``tiny.py``); the harness finds
their configuration, mix, cell and metric files by name, and the
generators, readers and reference beside itself.  No chip is needed:
``run_cell(..., require_tpu=False)`` skips only the look for one."""
import json

import pytest

from bench import run, spec
from bench.harness import run_cell
from bench.tests import tiny

SEED = 2 ** 33 + 11
CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    extra = ("throwaway_mix", {**tiny.SESSIONS, "system_len": 16})
    return tiny.write_root(str(tmp_path_factory.mktemp("bench")), extra)


def _run(root, name, **kw):
    cell = spec.load_cell(name, root)
    kw.setdefault("trace", False)
    return cell, run_cell(cell, SEED, 1.5, root=root, require_tpu=False,
                          cache=False, log=lambda m: None, **kw)


def test_open_loop_run_prints_the_contract_line(root):
    cell, res = _run(root, "tiny.tiny_sessions")
    line = json.loads(run.result_line(res))
    assert list(line)[:5] == CONTRACT and list(line)[-1] == "check"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"output_tokens_per_s", "tbt_p99_ms",
            "setup_s"} == set(line["metrics"])
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["check"]["widest_gap"]["limit"] == tiny.CHECK["gap_limit"]


def test_closed_loop_run_reports_throughput_only(root):
    cell, res = _run(root, "tiny.tiny_batch")
    line = json.loads(run.result_line(res))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_counter_metrics(root):
    cell, res = _run(root, "tiny.tiny_sessions", trace=True)
    names = set(res["metrics"])
    assert {"sched.prefix_hit_share", "sched.batch_occupancy",
            "translation.rsw_hit_share"} <= names
    for m in res["metrics"].values():
        assert m["unit"] == "%" or m["unit"] == "ms"
    assert 0 < res["metrics"]["sched.batch_occupancy"]["value"] <= 100
    assert res["correct"] is True


def test_a_new_mix_is_found_by_name(root):
    cell = spec.load_cell("tiny.throwaway_mix", root)
    assert cell.mix["system_len"] == 16 and cell.mix["kind"] == "open_sessions"
    gen = spec.load_module(cell.search, "mixes", cell.mix["kind"])
    items = gen.Traffic(cell.mix, cell.config, cell.params, 3, 5.0).start()
    assert items and all(len(i.prompt) % 8 == 0 for i in items)


def test_main_refuses_a_platform_without_a_tpu(capsys):
    rc = run.main(["--workload", "granite-8b.sessions", "--seed", "5",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out


def test_altered_token_is_not_correct(root, monkeypatch):
    """The timed path broken underneath: every decoded token is shifted by
    one where the decode step produces it."""
    import repro.serve.engine as E
    orig = E.make_serve_step
    vocab = tiny.TINY["vocab_size"]

    def broken(*a, **k):
        step = orig(*a, **k)

        def serve_step(params, dstate, tokens, active=None, *, sample=False):
            logits, st, stats = step(params, dstate, tokens, active,
                                     sample=sample)
            stats = dict(stats)
            stats["next_token"] = (stats["next_token"] + 1) % vocab
            return logits, st, stats
        return serve_step

    monkeypatch.setattr(E, "make_serve_step", broken)
    _, res = _run(root, "tiny.tiny_sessions")
    assert res["correct"] is False
    assert res["check"]["widest_gap"]["value"] > tiny.CHECK["gap_limit"]


def test_fp8_control_is_not_correct(root):
    """The control: the reference in float8 in the program's place reads a
    gap above the cell's limit."""
    _, res = _run(root, "tiny.tiny_batch", controls=("fp8",))
    assert res["_gaps"]["served"] <= tiny.CHECK["gap_limit"]
    assert res["_gaps"]["fp8"] > tiny.CHECK["gap_limit"]
