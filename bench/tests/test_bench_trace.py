"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: three rounds of ``serve_step`` and ``prefill_step``
under ``bench.poll``, 5 ms of ``bench.wait`` after each)."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(DATA)


def test_programs_are_found_by_module_name(red):
    assert red["devices"] == 1
    assert red["module_n"]["jit_serve_step"] == 3
    assert red["module_n"]["jit_prefill_step"] == 3
    sec, n = trace.module_time(red, r"^jit_serve_step")
    assert n == 3 and 0 < sec < 3e-3


def test_busy_and_idle_fill_the_window(red):
    assert 0 < red["busy_s"] < red["window_s"]
    # three 5 ms sleeps are idle, so at least 15 ms of the window is
    assert red["window_s"] - red["busy_s"] >= 0.015
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert gaps[0] >= 0.004


def test_idle_gaps_are_labelled_by_host_spans(red):
    labels = [name for name, _ in red["idle_gaps"]]
    assert any(name.startswith("bench.wait") for name in labels)
    assert all(name for name in labels)


def test_ops_are_attributed_to_their_programs(red):
    names = [n for n, _ in red["device_ops"]]
    assert names and all("/" in n for n in names)
    assert any(n.startswith("jit_serve_step/") for n in names)
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] + 1e-9


def test_union_and_clip():
    iv = trace.union([(0, 2), (1, 3), (5, 6)])
    assert iv == [(0, 3), (5, 6)]
    assert trace.total(trace.clip(iv, 1, 5.5)) == 2.5
    assert trace.op_name("%fusion.12 = bf16[8] fusion(%a)") == "fusion.12"
    assert trace.COLLECTIVE.match("all-reduce-start.3")
    assert not trace.COLLECTIVE.match("fusion.3")
