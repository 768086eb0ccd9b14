"""Graceful degradation under overload (ISSUE 6).

Tentpole contract: with a pool sized for ~4 concurrent sequences and 12
submitted, EVERY request completes and every token stream is bit-identical
to an uncontended run — the engine preempts victim sequences to the host
KV tier and resumes them instead of failing.  ``PoolExhausted`` survives
only for requests that can NEVER run, and carries structured occupancy
diagnostics.  A chaos injector (``ServeFaultInjector``) forces allocation
denials and preemptions at adversarial step points; the same differential
oracle must hold under any injection schedule.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core import HybridConfig, HybridKVManager, PoolExhausted, FLEX
from repro.models import model_dims, init_params
from repro.runtime import (ServeFaultInjector, InjectedFault,
                           InjectedAllocFault, InjectedStepFault)
from repro.serve import Engine, Request, EngineConfig
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (default_victim, FIFOScheduler,
                                   ShortestPromptFirst,
                                   PriorityAgingScheduler)

try:
    from hypothesis import given, settings, strategies as st, HealthCheck
    HAVE_HYPOTHESIS = True
except ImportError:                        # optional dev dependency
    HAVE_HYPOTHESIS = False


_SETUP_CACHE = {}


def _setup(arch="granite-8b"):
    """2-layer reduced model: the suite runs many engine pairs, so keep
    per-engine compile cost minimal (bucket shapes recur across runs and
    hit the jit cache)."""
    if arch not in _SETUP_CACHE:
        cfg = dataclasses.replace(reduced(ARCHS[arch]), num_layers=2)
        dims = model_dims(cfg, tp=1)
        params = init_params(jax.random.PRNGKey(2), cfg, dims)
        _SETUP_CACHE[arch] = (cfg, params)
    return _SETUP_CACHE[arch]


def _drain(eng, max_steps=900, invariants=True):
    """Poll to completion, asserting pool consistency after every step.
    Returns {seq_id: [token, ...]} per-request streams."""
    outs = {}
    for _ in range(max_steps):
        for ro in eng.poll():
            outs.setdefault(ro.seq_id, []).extend(ro.new_token_ids)
        if invariants:
            eng.manager.check_invariants()
        if not eng.has_unfinished():
            return outs
    raise AssertionError("engine failed to drain")


def _overload_run(cfg, params, headroom, *, n_req=12, max_new=20,
                  sampling=None, **ekw):
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=8 * bs, pool_headroom=headroom,
        auto_release=True, **ekw))
    rng = np.random.RandomState(7)
    for i in range(n_req):
        eng.submit(Request(
            seq_id=i, prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
            max_new_tokens=max_new,
            sampling=sampling if sampling is not None else SamplingParams()))
    outs = _drain(eng)
    assert set(outs) == set(range(n_req))
    return outs, eng


# --------------------------------------------------- the overload oracle

SAMPLED = SamplingParams(temperature=0.8, top_k=40, seed=123)


@pytest.mark.parametrize("spec,sampling", [
    (None, None), (None, SAMPLED), ("ngram", None), ("ngram", SAMPLED),
], ids=["greedy", "sampled", "spec-greedy", "spec-sampled"])
def test_overload_streams_bit_identical(spec, sampling):
    """Pool sized for 4 sequences (16 slots), 12 submitted: every request
    finishes, zero PoolExhausted, and each stream equals the uncontended
    (4x pool) run token for token — through real preempt/resume cycles."""
    cfg, params = _setup()
    oracle, ref = _overload_run(cfg, params, 2.0, sampling=sampling,
                                spec_decode=spec)
    tight, eng = _overload_run(cfg, params, 0.5, sampling=sampling,
                               spec_decode=spec)
    assert ref.hybrid_cfg.total_slots == 4 * eng.hybrid_cfg.total_slots
    for sid in oracle:
        assert tight[sid] == oracle[sid], f"seq {sid} diverged"
        assert len(tight[sid]) == 20
    ov = eng.stats()["overload"]
    assert ov["preempted_seqs"] > 0, "overload never exercised the tier"
    assert ov["resumed_seqs"] == ov["preempted_seqs"]
    assert ov["host_tier_seqs"] == 0          # everyone came back
    assert ov["swap_bytes_in"] == ov["swap_bytes_out"] > 0
    # drained pool is leak-free: no mapped blocks, no registered seqs
    assert not eng.manager.blocks
    assert not eng.manager.seq_lengths
    m = eng.manager
    assert m.stats["swap_out_preempt"] == m.stats["swap_in_resume"] > 0


def test_overload_fail_policy_is_fail_fast():
    """``overload_policy="fail"`` reproduces the pre-ISSUE-6 ladder:
    admission is footprint-gated (serve only what provably fits), nothing
    is ever preempted, and the streams still match the oracle — the cost
    is concurrency, not correctness."""
    cfg, params = _setup()
    oracle, _ = _overload_run(cfg, params, 2.0)
    tight, eng = _overload_run(cfg, params, 0.5, overload_policy="fail")
    for sid in oracle:
        assert tight[sid] == oracle[sid]
    assert eng.stats()["overload"]["preempted_seqs"] == 0


def test_overload_with_priority_scheduler_and_shared_release():
    """The ladder composes with a non-FIFO policy: priority+aging picks
    victims by effective priority and still drains bit-identically."""
    cfg, params = _setup()
    oracle, _ = _overload_run(cfg, params, 2.0, scheduler="priority")
    tight, eng = _overload_run(cfg, params, 0.5, scheduler="priority")
    for sid in oracle:
        assert tight[sid] == oracle[sid]
    assert eng.stats()["overload"]["preempted_seqs"] > 0


# ------------------------------------------- un-admittable diagnostics

def test_unadmittable_prompt_raises_with_diagnostics():
    """A prompt whose blocks alone exceed the whole pool can never be
    admitted — preemption cannot help, so PoolExhausted survives and
    carries structured occupancy diagnostics."""
    cfg, params = _setup()
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=24 * bs, pool_headroom=0.4))
    assert eng.hybrid_cfg.total_slots < 20
    big = Request(seq_id=0, max_new_tokens=2,
                  prompt=np.arange(20 * bs) % cfg.vocab_size)
    eng.submit(big)
    with pytest.raises(PoolExhausted, match="cannot be admitted") as ei:
        for _ in range(10):
            eng.poll()
    d = ei.value.diag
    for key in ("pool_blocks", "mapped_blocks", "free_flex", "queued",
                "live", "finished_unreleased", "preempted"):
        assert key in d, key
    assert d["pool_blocks"] < 20
    # the diagnostics ride the message too (the operator-visible half)
    assert "pool_blocks=" in str(ei.value)


def test_pool_exhausted_diag_construction():
    e = PoolExhausted("no room", live=3, queued=2)
    assert e.diag == {"live": 3, "queued": 2}
    assert str(e) == "no room [live=3 queued=2]"
    assert str(PoolExhausted("plain")) == "plain"


def test_finished_unreleased_still_raises():
    """auto_release=False with every slot parked on finished sequences is
    a genuine deadlock (the caller must release) — preemption of FINISHED
    sequences is never attempted, so poll() still raises."""
    cfg, params = _setup()
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=4 * bs, auto_release=False))
    rng = np.random.RandomState(3)
    for i in range(3):
        eng.submit(Request(seq_id=i, max_new_tokens=2,
                           prompt=rng.randint(0, cfg.vocab_size, bs)))
    with pytest.raises(PoolExhausted, match="finished") as ei:
        for _ in range(40):
            eng.poll()
    assert ei.value.diag["finished_unreleased"] == 2


# ----------------------------------------------- chaos: forced schedules

def _chaos_replay(preempt_at=(), alloc_fail_at=(), seed=None,
                  preempt_rate=0.0, alloc_fail_rate=0.0, spec=None,
                  n_req=6, headroom=2.0):
    """Differential chaos harness (fixed replays AND the fuzzer drive
    this): run a clean engine and an injected engine on the same
    workload; every request's stream must match bit-for-bit."""
    cfg, params = _setup()

    def run(inj):
        outs, eng = _overload_run(cfg, params, headroom, n_req=n_req,
                                  max_new=12, spec_decode=spec,
                                  fault_injector=inj)
        return outs, eng

    clean, _ = run(None)
    inj = ServeFaultInjector(preempt_at=preempt_at,
                             alloc_fail_at=alloc_fail_at, seed=seed,
                             preempt_rate=preempt_rate,
                             alloc_fail_rate=alloc_fail_rate)
    chaos, eng = run(inj)
    for sid in clean:
        assert chaos[sid] == clean[sid], f"seq {sid} diverged under chaos"
    assert not eng.manager.blocks and not eng.manager.seq_lengths
    return inj, eng


def test_forced_preempt_pre_and_post():
    """Preemptions forced at both safe points — before admission (tears a
    victim out between prefill chunks) and after the commit (between a
    spec window's verify/commit and the next dispatch) — plus injected
    admission/decode allocation denials, all stream-invisible."""
    inj, eng = _chaos_replay(
        preempt_at=[(3, "pre", "auto"), (6, "post", 1), (9, "pre", "auto")],
        alloc_fail_at=[(4, "admit"), (7, "decode")])
    fired = [ev for ev in inj.log if ev[0] == "preempt"]
    assert len(fired) == 3
    assert eng.stats()["overload"]["request_preempts"] >= 3


def test_forced_preempt_mid_spec_window():
    """Under speculation the post-commit point sits exactly between a
    verify/commit and the next draft dispatch; preempting there must not
    perturb the lossless acceptance stream."""
    inj, _ = _chaos_replay(
        preempt_at=[(4, "post", "auto"), (7, "pre", 2)], spec="ngram")
    assert inj.faults()["preempt"] == 2


def test_forced_preempt_mid_chunk_prefill():
    """A victim preempted while its prompt is mid-chunk resumes as the
    engine-owned chunk request and finishes prefill via the normal
    prefix-KV path.  A tiny prefill budget keeps prompts mid-chunk for
    several steps so the early-step schedule reliably catches one."""
    cfg, params = _setup()
    bs = cfg.kv_block_size

    def run(inj):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=4, max_seq_len=8 * bs, pool_headroom=2.0,
            auto_release=True, prefill_budget=bs, fault_injector=inj))
        rng = np.random.RandomState(11)
        for i in range(3):
            eng.submit(Request(
                seq_id=i, prompt=rng.randint(0, cfg.vocab_size, 4 * bs),
                max_new_tokens=6))
        return _drain(eng), eng

    clean, _ = run(None)
    inj = ServeFaultInjector(preempt_at=[(2, "pre", 0), (5, "pre", "auto")])
    chaos, eng = run(inj)
    assert chaos == clean
    assert eng.stats()["overload"]["request_preempts"] >= 1


def test_injector_schedule_validation_and_log():
    with pytest.raises(ValueError, match="phase"):
        ServeFaultInjector(preempt_at=[(1, "mid", "auto")])
    inj = ServeFaultInjector(alloc_fail_at=[(2, "admit")])
    assert inj.alloc_unavailable(1, "admit") is False
    assert inj.alloc_unavailable(2, "admit") is True
    assert inj.alloc_unavailable(2, "admit") is False      # fires once
    assert inj.faults() == {"alloc": 1, "preempt": 0, "step": 0}
    assert issubclass(InjectedAllocFault, InjectedFault)
    assert issubclass(InjectedStepFault, InjectedFault)
    assert InjectedAllocFault.kind == "alloc"


def test_fixed_chaos_schedules():
    """Deterministic instances of the chaos-replay harness (the same
    helper the hypothesis fuzzer drives), so the replay logic itself is
    exercised even where hypothesis is not installed."""
    _chaos_replay(preempt_at=[(2, "pre", "auto")], seed=5,
                  preempt_rate=0.15, headroom=1.0)
    _chaos_replay(alloc_fail_at=[(3, "decode"), (5, "resume")],
                  preempt_at=[(4, "post", 0)], headroom=0.75)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=6,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def test_chaos_schedule_fuzz(data):
        """Random (injection schedule x pool pressure x spec) chaos: the
        differential oracle holds for ANY schedule, generalizing the
        fixed replays above."""
        n_pre = data.draw(st.integers(0, 3), label="n_preempts")
        preempts = [(data.draw(st.integers(1, 10), label=f"pstep{i}"),
                     data.draw(st.sampled_from(["pre", "post"]),
                               label=f"pphase{i}"),
                     data.draw(st.sampled_from(["auto", 0, 1, 2]),
                               label=f"ptarget{i}"))
                    for i in range(n_pre)]
        n_alloc = data.draw(st.integers(0, 2), label="n_allocs")
        allocs = [(data.draw(st.integers(1, 10), label=f"astep{i}"),
                   data.draw(st.sampled_from(["admit", "decode", "resume"]),
                             label=f"apoint{i}"))
                  for i in range(n_alloc)]
        headroom = data.draw(st.sampled_from([0.75, 1.0, 2.0]),
                             label="headroom")
        spec = data.draw(st.sampled_from([None, "ngram"]), label="spec")
        _chaos_replay(preempt_at=preempts, alloc_fail_at=allocs,
                      headroom=headroom, spec=spec)
else:
    def test_chaos_schedule_fuzz():
        pytest.skip("hypothesis not installed")


# ---------------------------------------------- recurrent-family preempt

def test_recurrent_family_preempt_resume():
    """mamba2 has no KV blocks — the host tier carries the ssm/conv rows
    only — and the same stream-invisibility contract holds."""
    cfg, params = _setup("mamba2-130m")
    bs = cfg.kv_block_size

    def run(inj):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=4, max_seq_len=8 * bs, auto_release=True,
            fault_injector=inj))
        rng = np.random.RandomState(5)
        for i in range(4):
            eng.submit(Request(
                seq_id=i, prompt=rng.randint(0, cfg.vocab_size, bs),
                max_new_tokens=8))
        return _drain(eng, invariants=False), eng

    clean, _ = run(None)
    inj = ServeFaultInjector(preempt_at=[(3, "post", 1), (5, "pre", "auto")])
    chaos, eng = run(inj)
    assert chaos == clean
    ov = eng.stats()["overload"]
    assert ov["request_preempts"] == 2
    assert ov["swap_bytes_out"] == ov["swap_bytes_in"] > 0


# ------------------------------------------------ manager-level contract

def _mgr(**kw):
    kw.setdefault("total_slots", 32)
    kw.setdefault("assoc", 4)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_blocks_per_seq", 8)
    return HybridKVManager(HybridConfig(**kw))


def test_manager_preempt_resume_roundtrip():
    m = _mgr()
    m.register_sequence(0)
    for b in range(5):
        assert m.allocate_block(0, b).slot >= 0
    before = m.stats["swap_out"]
    saved = m.preempt(0)
    assert [b for b, _ in saved] == list(range(5))
    assert all(w for _, w in saved)
    assert not m.blocks and 0 not in m._seq_ids
    assert m.stats["preempt_out"] == 1
    assert m.stats["swap_out_preempt"] == 5
    assert m.stats["swap_out"] == before + 5
    m.check_invariants()
    newmap = m.resume(0, saved)
    assert sorted(newmap) == list(range(5))
    for b in range(5):
        info = m.blocks[m.cfg.vpn(m.seq_slot(0), b)]
        assert info.slot >= 0 and info.writable
    assert m.stats["preempt_in"] == 1
    assert m.stats["swap_in_resume"] == 5
    m.check_invariants()


def test_manager_preempt_shared_prefix_coowner_safe():
    """Preempting a sharer only drops ITS reference: the co-owner's
    physical slots (and read-only marks) survive untouched, and the
    resumed sequence gets private writable state only where it had it."""
    m = _mgr()
    m.register_sequence(0)
    for b in range(4):
        m.allocate_block(0, b)
    m.register_sequence(1)
    m.share_prefix(0, 1, 2)                       # blocks 0,1 shared
    m.allocate_block(1, 2)                        # private tail
    owner_slots = {b: m.lookup(0, b)[0] for b in range(4)}
    saved = m.preempt(1)
    assert {b: m.lookup(0, b)[0] for b in range(4)} == owner_slots
    m.check_invariants()
    m.resume(1, saved)
    m.check_invariants()
    # shared blocks came back read-only (a prefix reference), private
    # tail came back writable

    def winfo(sid, b):
        return m.blocks[m.cfg.vpn(m.seq_slot(sid), b)].writable

    assert not winfo(1, 0) and not winfo(1, 1)
    assert winfo(1, 2)
    # and the resumed refs share or copy, but never steal: owner intact
    assert {b: m.lookup(0, b)[0] for b in range(4)} == owner_slots


def test_manager_preempt_restrictive_only_rejected():
    m = _mgr(mode="restrictive_only")
    m.register_sequence(0)
    with pytest.raises(ValueError, match="restorable"):
        m.preempt(0)


def test_alloc_ledger_exact_dry_run():
    """The ledger's all-or-nothing reserve answers exactly what a real
    allocation round would: per-set empty ways first, then flex slots."""
    m = _mgr(total_slots=16, max_blocks_per_seq=4)   # 12 rest + 4 flex
    m.register_sequence(0)
    led = m.alloc_ledger()
    want = [m.cfg.vpn(m.seq_slot(0), b) for b in range(4)]
    assert led.reserve(want)
    for b in range(4):
        m.allocate_block(0, b)
    # a fresh ledger reflects the consumed capacity
    m.register_sequence(1)
    led2 = m.alloc_ledger()
    vpns = [m.cfg.vpn(m.seq_slot(1), b) for b in range(4)]
    ok = led2.reserve(vpns)
    # verify against ground truth: replay on the real manager
    slots = [m.allocate_block(1, b).slot for b in range(4)]
    assert ok == all(s >= 0 for s in slots)
    # reserve is all-or-nothing: a failing batch consumes nothing
    m2 = _mgr(total_slots=8, max_blocks_per_seq=8, restseg_fraction=0.0)
    m2.register_sequence(0)
    led3 = m2.alloc_ledger()
    vp = [m2.cfg.vpn(m2.seq_slot(0), b) for b in range(8)]
    assert led3.reserve(vp[:6])                 # 6 of 8 flex slots
    assert not led3.reserve(vp[6:] + [vp[7] + 8])   # 3 needed, 2 left
    assert led3.reserve(vp[6:])                 # the failure reserved 0


def test_swap_counter_unification_invariant():
    """stats["swap_out"/"swap_in"] totals are mutated only through the
    counting helpers, so they always equal the per-reason breakdown —
    and check_invariants cross-checks exactly that."""
    m = _mgr(total_slots=16, max_blocks_per_seq=8, restseg_fraction=0.0)
    m.register_sequence(0)
    for b in range(8):
        m.allocate_block(0, b)
    m.register_sequence(1)
    for b in range(8):
        m.allocate_block(1, b)                 # pool-pressure swap-outs
    assert m.stats["swap_out"] == sum(
        v for k, v in m.stats.items() if k.startswith("swap_out_"))
    m.check_invariants()
    m.stats["swap_out"] += 1                   # simulate a rogue bump
    with pytest.raises(AssertionError, match="swap_out"):
        m.check_invariants()


# -------------------------------------------------- victim-policy units

class _St:
    def __init__(self, seq_id, arrival, last_step, prompt_len=8,
                 priority=0):
        self.request = type("R", (), {
            "seq_id": seq_id, "priority": priority,
            "prompt": np.zeros(prompt_len)})()
        self.arrival = arrival
        self.last_step = last_step


def test_default_victim_lru_then_youngest():
    a = _St(0, arrival=0, last_step=5)
    b = _St(1, arrival=2, last_step=3)          # least recent commit
    c = _St(2, arrival=4, last_step=3)          # tie: younger arrival
    assert default_victim([a, b], now=9) is b
    assert default_victim([a, b, c], now=9) is c
    assert FIFOScheduler.victim([a, c], 9) is c
    assert FIFOScheduler().should_preempt(a.request, 0, b, 9) is False


def test_spf_victim_longest_prompt():
    a = _St(0, arrival=0, last_step=1, prompt_len=4)
    b = _St(1, arrival=1, last_step=9, prompt_len=32)
    assert ShortestPromptFirst.victim([a, b], now=9) is b
    assert ShortestPromptFirst().should_preempt(a.request, 0, b, 9) is False


def test_priority_victim_and_admission_gate():
    s = PriorityAgingScheduler(aging_rate=0.0)
    lo = _St(0, arrival=0, last_step=8, priority=1)
    hi = _St(1, arrival=0, last_step=2, priority=9)
    assert s.victim([lo, hi], now=10) is lo
    urgent = _St(2, arrival=10, last_step=0, priority=5).request
    assert s.should_preempt(urgent, 10, lo, 10) is True      # 5 > 1
    assert s.should_preempt(urgent, 10, hi, 10) is False     # 5 < 9
    equal = _St(3, arrival=10, last_step=0, priority=1).request
    assert s.should_preempt(equal, 10, lo, 10) is False      # strict >


# ------------------------------------------------- serving stats surface

def test_overload_stats_block_and_per_request_shape():
    """stats() carries the aggregate overload block; the pinned
    per-request row schema is unchanged (test_serving_api pins it)."""
    cfg, params = _setup()
    _, eng = _overload_run(cfg, params, 0.5, n_req=8, max_new=20)
    s = eng.stats()
    ov = s["overload"]
    assert set(ov) == {"preempted_seqs", "resumed_seqs", "host_tier_seqs",
                       "swap_bytes_out", "swap_bytes_in",
                       "request_preempts", "request_resumes",
                       "dropped_request_preempts",
                       "dropped_request_resumes"}
    assert ov["preempted_seqs"] > 0
    assert ov["request_resumes"] == ov["request_preempts"]
    for row in s["per_request"].values():
        assert set(row) == {"rsw_hits", "flex_walks", "swap_faults",
                            "drafted", "accepted", "cached_blocks",
                            "preempts", "resumes", "queue_wait_s",
                            "admitted_at"}
    # no ids were reused in this run: the rows carry the whole account
    assert (sum(r["preempts"] for r in s["per_request"].values())
            == ov["request_preempts"])


def test_request_preempt_counts_survive_seq_id_reuse():
    """ISSUE 9 satellite 1: ``request_preempts`` used to be a sum of
    ``st.preempts`` over ``self._states`` — resubmitting a finished
    seq_id replaced its state and the preempt history silently
    vanished.  Now the globals are MONOTONE engine counters; submit()
    banks the dropped row's counts, and
    ``sum(per-request rows) + dropped == global`` holds across reuse
    (also asserted by ``Engine.check_invariants``)."""
    cfg, params = _setup()
    bs = cfg.kv_block_size
    inj = ServeFaultInjector(preempt_at=[(2, "post", 0), (4, "pre", 1)])
    eng = Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=8 * bs, pool_headroom=2.0,
        auto_release=True, fault_injector=inj))
    rng = np.random.RandomState(3)

    def submit_round():
        for i in range(3):
            eng.submit(Request(
                seq_id=i, prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                max_new_tokens=6))

    submit_round()
    _drain(eng)
    s = eng.stats()
    ov = s["overload"]
    assert ov["request_preempts"] == 2 == ov["request_resumes"]
    assert ov["dropped_request_preempts"] == 0
    assert sum(r["preempts"] for r in s["per_request"].values()) == 2

    # reuse EVERY seq_id: submit() drops the finished rows and banks
    # their counts — the pre-fix row sum reported 0 preempts here
    submit_round()
    _drain(eng)
    s = eng.stats()
    ov = s["overload"]
    assert ov["request_preempts"] == 2 == ov["request_resumes"]  # monotone
    assert ov["dropped_request_preempts"] == 2
    assert ov["dropped_request_resumes"] == 2
    rows = s["per_request"]
    assert sum(r["preempts"] for r in rows.values()) == 0   # fresh rows
    assert sum(r["resumes"] for r in rows.values()) == 0
    assert (sum(r["preempts"] for r in rows.values())
            + ov["dropped_request_preempts"] == ov["request_preempts"])
    eng.check_invariants()
