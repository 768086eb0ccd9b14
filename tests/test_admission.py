"""Admission-scheduler tests (ISSUE 2).

The tentpole contract: batched, chunked, budget-bounded admission must be
OBSERVATIONALLY IDENTICAL to the old one-request-at-a-time serving — same
greedy tokens, no cross-sequence interference — while prefill never
touches the state of non-participating slots (the ``jnp.full_like``
ctx_len stomp this PR fixes) and finished sequences recycle their slots
under sustained load.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import model_dims, init_params
from repro.serve import Engine, Request
from repro.serve.decode import DecodeSpec, init_decode_state
from repro.serve.prefill import make_prefill_step


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["granite-8b"])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    return cfg, dims, params


def _drain(eng):
    steps = 0
    while eng.waiting or any(not r.done for r in eng.requests.values()):
        eng.step()
        steps += 1
        assert steps < 200, "engine failed to drain"
    return steps


# ------------------------------------------------ prefill ctx_len regression

def test_prefill_never_mutates_nonparticipating_ctx(setup):
    """The multi-sequence prefill scatters ctx_len to participating slots
    ONLY.  The pre-fix code did ``jnp.full_like(ctx_len, ctx)``, stomping
    every live sequence's context length."""
    cfg, dims, params = setup
    bs = cfg.kv_block_size
    spec = DecodeSpec(block_size=bs, max_blocks_per_seq=4,
                      slots_per_group=16, n_sets=2, assoc=4)
    dstate = init_decode_state(cfg, dims, spec, 4, 1)
    before = np.asarray([5, 7, 0, 9], np.int32)
    dstate["ctx_len"] = jnp.asarray(before)
    kp_before = np.asarray(dstate["k_pool"])
    pf = make_prefill_step(cfg, dims, spec, mesh=None)
    _, ns, stats = jax.jit(pf)(
        params, dstate,
        {"tokens": jnp.zeros((2, 2 * bs), jnp.int32)},
        jnp.asarray([[2, 3], [-1, -1]], jnp.int32),   # row 1: pad row
        jnp.asarray([2, -1], jnp.int32),              # participant slot 2
        jnp.asarray([2 * bs, 0], jnp.int32),
        jnp.asarray([2 * bs - 1, 0], jnp.int32))
    got = np.asarray(ns["ctx_len"])
    assert got[2] == 2 * bs                      # participant updated
    np.testing.assert_array_equal(got[[0, 1, 3]], before[[0, 1, 3]])
    # the -1 pad row must be dropped, not clamped onto pool slot 0
    np.testing.assert_array_equal(np.asarray(ns["k_pool"])[:, 0],
                                  kp_before[:, 0])
    assert stats["next_token"].shape == (2,)


def test_engine_prefill_leaves_live_slots_alone(setup):
    """Admitting (and chunking) a new prompt mid-decode must not disturb a
    live sequence's context length or generation."""
    cfg, _, params = setup
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, max_batch=4, max_seq_len=8 * bs,
                 prefill_budget=bs)             # 1 block/step: forces chunks
    rng = np.random.RandomState(0)
    a = Request(seq_id=0, prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                max_new_tokens=12)
    eng.add_request(a)
    slot_a = eng._slot_of[0]
    ctx_a = int(eng._ctx_host[slot_a])
    eng.submit(Request(seq_id=1,
                       prompt=rng.randint(0, cfg.vocab_size, 4 * bs),
                       max_new_tokens=4))
    for k in range(1, 4):                       # B is mid-prefill throughout
        eng.step()
        assert eng._prefilling.get(1, 4 * bs) < 4 * bs
        # A decoded exactly once per step; B's chunks never touched it
        assert int(eng._ctx_host[slot_a]) == ctx_a + k
        np.testing.assert_array_equal(np.asarray(eng.dstate["ctx_len"]),
                                      eng._ctx_host)


# -------------------------------------------------- sequential equivalence

def test_interleaved_admission_matches_sequential(setup):
    """Admitting prompts through the batched/chunked scheduler mid-decode
    produces token-for-token the same generations as serving each request
    alone (same engine geometry, one at a time)."""
    cfg, _, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(42)
    prompts = [rng.randint(0, cfg.vocab_size, n * bs)
               for n in (2, 4, 2, 3)]
    n_new = [6, 5, 6, 4]

    def engine():
        return Engine(cfg, params, max_batch=4, max_seq_len=8 * bs)

    # sequential one-at-a-time reference (fresh pool per request)
    ref = []
    for p, n in zip(prompts, n_new):
        eng = engine()
        r = Request(seq_id=0, prompt=p, max_new_tokens=n)
        eng.add_request(r)
        _drain(eng)
        ref.append(list(r.generated))

    # interleaved: two up front, the rest submitted mid-decode; a small
    # budget chunks the 4-block prompt across steps
    eng = engine()
    eng.prefill_budget = 2 * bs
    reqs = [Request(seq_id=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    eng.step()
    eng.step()
    eng.submit(reqs[2])                          # mid-decode admission
    eng.submit(reqs[3])
    _drain(eng)
    for i, r in enumerate(reqs):
        assert list(r.generated) == ref[i], f"request {i} diverged"
    eng.manager.check_invariants()


@pytest.mark.parametrize("arch", ["mamba2-130m", "jamba-1.5-large-398b"])
def test_recurrent_family_nonpow2_prompt_matches_full_forward(arch):
    """Recurrent (SSM/conv) state must not integrate the bucket's pad
    tail: ssm/hybrid rows ride the pow2 buckets with a per-row
    ``seq_len`` mask that zeroes dt past the real length, making every
    pad position an exact identity transition (PR 4; PR 2 used exact
    lengths instead).  The oracle is a full re-forward per step (NOT
    another engine path — both engine paths share the bucketized
    prefill, so comparing them would miss this)."""
    from repro.models import forward, FwdOptions
    cfg = reduced(ARCHS[arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    bs = cfg.kv_block_size
    prompt = np.random.RandomState(5).randint(0, cfg.vocab_size, 3 * bs)
    n_new = 4

    toks, ref = list(prompt), []
    for _ in range(n_new):
        logits, _, _ = forward(params, {"tokens": jnp.asarray(toks)[None]},
                               cfg, dims, FwdOptions())
        ref.append(int(jnp.argmax(logits[0, -1])))
        toks.append(ref[-1])

    eng = Engine(cfg, params, max_batch=2, max_seq_len=8 * bs)
    r = Request(seq_id=0, prompt=prompt, max_new_tokens=n_new)
    eng.submit(r)
    _drain(eng)
    assert list(r.generated) == ref


def test_share_source_released_before_sharer_admitted(setup):
    """Prefix sharing degrades to plain prefill (same tokens, no crash)
    when the source finished and auto-released while the sharer queued."""
    cfg, _, params = setup
    bs = cfg.kv_block_size
    prompt = np.random.RandomState(11).randint(0, cfg.vocab_size, 2 * bs)

    solo = Request(seq_id=9, prompt=prompt, max_new_tokens=3)
    eng0 = Engine(cfg, params, max_batch=1, max_seq_len=6 * bs)
    eng0.add_request(solo)
    _drain(eng0)

    eng = Engine(cfg, params, max_batch=1, max_seq_len=6 * bs,
                 auto_release=True)
    src = Request(seq_id=0, prompt=prompt, max_new_tokens=3)
    eng.add_request(src)
    # max_batch=1: the sharer cannot register until src releases — by
    # which time its share source is gone
    dup = Request(seq_id=1, prompt=prompt, max_new_tokens=3)
    eng.submit(dup, share_prefix_from=0, shared_blocks=2)
    _drain(eng)
    assert list(src.generated) == list(solo.generated)
    assert list(dup.generated) == list(solo.generated)
    eng.manager.check_invariants()


def test_empty_prompt_rejected(setup):
    cfg, _, params = setup
    eng = Engine(cfg, params, max_batch=2,
                 max_seq_len=4 * cfg.kv_block_size)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(seq_id=0, prompt=np.zeros(0, np.int64)))


# ------------------------------------------------------ EOS + slot recycle

def test_eos_terminates_early_and_releases(setup):
    cfg, _, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab_size, 2 * bs)
    probe = Request(seq_id=0, prompt=prompt, max_new_tokens=8)
    eng = Engine(cfg, params, max_batch=2, max_seq_len=6 * bs)
    eng.add_request(probe)
    _drain(eng)
    assert len(probe.generated) == 8

    # the eos must be a token the stream has not produced before: random
    # weights repeat ids, and an earlier occurrence would (rightly) stop
    # the stream there
    gen = probe.generated
    stop = next((i for i in range(1, len(gen)) if gen[i] not in gen[:i]),
                None)
    assert stop is not None, f"stream {gen} repeats a single token"
    eng2 = Engine(cfg, params, max_batch=2, max_seq_len=6 * bs,
                  auto_release=True)
    r = Request(seq_id=0, prompt=prompt, max_new_tokens=8,
                eos_token=gen[stop])
    eng2.add_request(r)
    _drain(eng2)
    assert r.done
    assert list(r.generated) == gen[:stop + 1]        # stopped ON the eos
    assert r._engine_state.finish_reason == "stop"
    assert 0 in eng2.finished and 0 not in eng2.requests
    assert not eng2._slot_of                          # slot freed
    eng2.manager.check_invariants()


def test_sustained_load_recycles_slots(setup):
    """More requests than batch slots: finished sequences auto-release and
    the queue drains through the recycled slots."""
    cfg, _, params = setup
    bs = cfg.kv_block_size
    rng = np.random.RandomState(9)
    eng = Engine(cfg, params, max_batch=2, max_seq_len=6 * bs,
                 auto_release=True)
    n_req = 5
    for sid in range(n_req):
        eng.submit(Request(seq_id=sid,
                           prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                           max_new_tokens=3))
    _drain(eng)
    assert len(eng.finished) == n_req
    assert all(len(r.generated) == 3 for r in eng.finished.values())
    assert len(eng.manager._free_seq_slots) == 2      # all slots recycled
    assert not eng.requests and not eng.waiting
    eng.manager.check_invariants()
