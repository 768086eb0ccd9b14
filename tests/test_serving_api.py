"""Request-centric serving API tests (ISSUE 3).

* EngineConfig replaces the kwarg pile; the legacy kwargs still work
  through a shim that warns exactly once per process;
* Request is an immutable submission (frozen dataclass, read-only
  prompt array) whose runtime state lives in the engine;
* Engine.poll() / stream() surface RequestOutput snapshots whose
  concatenated deltas reconstruct each request's full generation;
* stats()["per_request"] attributes RestSeg hits / flexible walks /
  swap faults per seq_id, and the per-request rows sum to the global
  counters fed back from decode telemetry.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import model_dims, init_params
from repro.serve import (Engine, EngineConfig, Request, RequestOutput,
                         SamplingParams)
import repro.serve.engine as engine_mod


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["granite-8b"])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    return cfg, params


# ------------------------------------------------------- config + shim

def test_legacy_kwargs_warn_exactly_once(setup, monkeypatch):
    cfg, params = setup
    bs = cfg.kv_block_size
    monkeypatch.setattr(engine_mod, "_LEGACY_KWARGS_WARNED", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        e1 = Engine(cfg, params, max_batch=2, max_seq_len=4 * bs)
        e2 = Engine(cfg, params, max_batch=2, max_seq_len=4 * bs,
                    mode="flexible_only")
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)
           and "EngineConfig" in str(x.message)]
    assert len(dep) == 1
    # the shim still configures faithfully
    assert e1.max_batch == 2 and e2.hybrid_cfg.mode == "flexible_only"


def test_config_and_kwargs_are_exclusive(setup):
    cfg, params = setup
    with pytest.raises(TypeError, match="not both"):
        Engine(cfg, params, EngineConfig(max_batch=2), max_seq_len=64)
    with pytest.raises(TypeError, match="unknown Engine kwargs"):
        Engine(cfg, params, batch_size=2)


# -------------------------------------------------------- immutability

def test_request_is_immutable():
    req = Request(seq_id=0, prompt=np.arange(4, dtype=np.int64),
                  sampling=SamplingParams(temperature=0.5))
    with pytest.raises(dataclasses.FrozenInstanceError):
        req.max_new_tokens = 3
    with pytest.raises(dataclasses.FrozenInstanceError):
        req.prompt = np.zeros(4, np.int64)
    with pytest.raises(ValueError):
        req.prompt[0] = 7                  # defensive read-only copy
    src = np.arange(4, dtype=np.int64)
    r2 = Request(seq_id=1, prompt=src)
    src[0] = 99                            # caller mutation is invisible
    assert r2.prompt[0] == 0


def test_sampling_params_validated():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    assert SamplingParams().is_greedy
    assert not SamplingParams(temperature=0.7).is_greedy


def test_duplicate_seq_id_rejected(setup):
    cfg, params = setup
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(max_batch=2,
                                           max_seq_len=4 * bs))
    prompt = np.zeros(bs, np.int64)
    eng.submit(Request(seq_id=0, prompt=prompt))
    with pytest.raises(ValueError, match="already queued"):
        eng.submit(Request(seq_id=0, prompt=prompt))


# -------------------------------------------------- poll / stream output

def test_stream_outputs_reconstruct_generations(setup):
    cfg, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(5)
    eng = Engine(cfg, params, EngineConfig(max_batch=2,
                                           max_seq_len=6 * bs,
                                           auto_release=True))
    reqs = [Request(seq_id=s,
                    prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                    max_new_tokens=n)
            for s, n in ((0, 5), (1, 3))]
    for r in reqs:
        eng.submit(r)
    deltas = {0: [], 1: []}
    finals = {}
    for out in eng.stream():
        assert isinstance(out, RequestOutput)
        deltas[out.seq_id].extend(out.new_token_ids)
        assert tuple(deltas[out.seq_id]) == out.token_ids
        if out.finished:
            assert out.seq_id not in finals     # reported exactly once
            finals[out.seq_id] = out
    for r in reqs:
        assert deltas[r.seq_id] == list(r.generated)
        assert len(deltas[r.seq_id]) == r.max_new_tokens
        assert finals[r.seq_id].finish_reason == "length"
    assert not eng.has_unfinished()


def test_eos_finish_reason_is_stop(setup):
    cfg, params = setup
    bs = cfg.kv_block_size
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, bs)
    probe = Request(seq_id=0, prompt=prompt, max_new_tokens=4)
    eng = Engine(cfg, params, EngineConfig(max_batch=1,
                                           max_seq_len=4 * bs))
    eng.submit(probe)
    outs = [o for o in eng.stream() if o.finished]
    assert outs[0].finish_reason == "length"

    eng2 = Engine(cfg, params, EngineConfig(max_batch=1,
                                            max_seq_len=4 * bs))
    r = Request(seq_id=0, prompt=prompt, max_new_tokens=4,
                eos_token=probe.generated[1])
    eng2.submit(r)
    fin = [o for o in eng2.stream() if o.finished][0]
    assert fin.finish_reason == "stop"
    assert fin.token_ids == tuple(probe.generated[:2])


def test_poll_without_work_returns_empty(setup):
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        max_batch=1, max_seq_len=4 * cfg.kv_block_size))
    assert eng.poll() == []


def test_stream_raises_instead_of_spinning_when_stuck(setup):
    """auto_release=False + more requests than slots: once every slot is
    held by a finished sequence, iteration must raise (release or
    auto_release would unstick it), not busy-loop forever."""
    cfg, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(13)
    eng = Engine(cfg, params, EngineConfig(max_batch=1,
                                           max_seq_len=4 * bs))
    for sid in (0, 1):
        eng.submit(Request(seq_id=sid,
                           prompt=rng.randint(0, cfg.vocab_size, bs),
                           max_new_tokens=2))
    from repro.core import PoolExhausted
    with pytest.raises(PoolExhausted, match="cannot be admitted"):
        for _ in eng.stream():
            pass
    eng.release(0)                      # unstick manually and finish
    for _ in eng.stream():
        pass
    assert len(eng._states[1].generated) == 2


def test_seq_id_reuse_after_finish_forgets_old_incarnation(setup):
    cfg, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(17)
    eng = Engine(cfg, params, EngineConfig(max_batch=1,
                                           max_seq_len=4 * bs,
                                           auto_release=True))
    first = Request(seq_id=0, prompt=rng.randint(0, cfg.vocab_size, bs),
                    max_new_tokens=2)
    eng.submit(first)
    while eng.has_unfinished():
        eng.step()
    assert 0 in eng.finished
    second = Request(seq_id=0, prompt=rng.randint(0, cfg.vocab_size, bs),
                     max_new_tokens=3)
    eng.submit(second)                  # reuse after finish is allowed
    assert 0 not in eng.finished        # old incarnation forgotten
    while eng.has_unfinished():
        eng.step()
    assert len(second.generated) == 3
    assert list(eng.stats()["per_request"]) == [0]


def test_seq_id_reuse_with_held_slot_raises(setup):
    """auto_release=False: a finished request still holds its slot, so
    reusing its id must raise with guidance, not inherit the old slot
    (or crash mid-step)."""
    cfg, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(19)
    eng = Engine(cfg, params, EngineConfig(max_batch=2,
                                           max_seq_len=4 * bs))
    eng.submit(Request(seq_id=0, prompt=rng.randint(0, cfg.vocab_size, bs),
                       max_new_tokens=2))
    while eng.has_unfinished():
        eng.step()
    with pytest.raises(ValueError, match="still holds its"):
        eng.submit(Request(seq_id=0,
                           prompt=rng.randint(0, cfg.vocab_size, bs)))
    eng.release(0)
    eng.submit(Request(seq_id=0,                 # fine after release
                       prompt=rng.randint(0, cfg.vocab_size, bs),
                       max_new_tokens=2))


def test_prefill_budget_below_block_size_rejected(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="prefill_budget"):
        Engine(cfg, params, EngineConfig(
            max_batch=2, max_seq_len=4 * cfg.kv_block_size,
            prefill_budget=cfg.kv_block_size - 1))


def test_scheduler_instance_cannot_be_shared_across_engines(setup):
    cfg, params = setup
    from repro.serve import PriorityAgingScheduler
    config = EngineConfig(max_batch=1,
                          max_seq_len=4 * cfg.kv_block_size,
                          scheduler=PriorityAgingScheduler(0.5))
    Engine(cfg, params, config)
    with pytest.raises(ValueError, match="already bound"):
        Engine(cfg, params, config)


def test_request_requires_prompt():
    with pytest.raises(TypeError):
        Request(seq_id=0)


# -------------------------------------------------- per-request telemetry

def test_stats_attributes_translation_per_request(setup):
    cfg, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(11)
    eng = Engine(cfg, params, EngineConfig(max_batch=2,
                                           max_seq_len=6 * bs))
    reqs = [Request(seq_id=s,
                    prompt=rng.randint(0, cfg.vocab_size, (s + 1) * bs),
                    max_new_tokens=6)
            for s in (0, 1)]
    for r in reqs:
        eng.submit(r)
    while eng.has_unfinished():
        eng.step()
    st = eng.stats()
    per = st["per_request"]
    assert set(per) == {0, 1}
    for row in per.values():
        assert set(row) == {"rsw_hits", "flex_walks", "swap_faults",
                            "drafted", "accepted", "cached_blocks",
                            "preempts", "resumes", "queue_wait_s",
                            "admitted_at"}
        # spec decode is off: no drafts were ever proposed
        assert row["drafted"] == row["accepted"] == 0
    # decode telemetry is attributed exhaustively: per-request rows sum
    # to the global counters record_device_stats accumulated
    assert sum(r["rsw_hits"] for r in per.values()) == st["rsw_hits"]
    assert sum(r["flex_walks"] for r in per.values()) == st["flex_walks"]
    total = st["rsw_hits"] + st["flex_walks"]
    assert total > 0
    # the longer prompt owns more blocks, so it must account for more
    # translations overall
    t0 = per[0]["rsw_hits"] + per[0]["flex_walks"]
    t1 = per[1]["rsw_hits"] + per[1]["flex_walks"]
    assert t1 > t0
