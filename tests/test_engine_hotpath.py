"""Hot-path regression tests for the translate-once decode step.

Guards the PR-1 invariants (DESIGN.md §translate-once):

* the hybrid translation primitive is dispatched EXACTLY once per
  serve_step trace — not once per attention layer, not once on host;
* the in-graph translation telemetry (slots / in_rest / accesses /
  mapped) is bit-identical to the host-side ``translate()`` oracle;
* the engine's dirty-delta TAR/SF/flex sync reproduces the full
  re-upload bit-for-bit under a randomized alloc/evict/share/promote
  workload;
* batched slot-migration copies match sequential per-copy application,
  including chained copies within one drain;
* ``Engine.step()`` performs one device->host fetch per step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core import (HybridConfig, HybridKVManager, translate, REST,
                        FLEX, SWAP)
from repro.models import model_dims, init_params
from repro.serve import Engine, Request
from repro.serve import decode as decode_mod
from repro.serve.decode import (DecodeSpec, init_decode_state,
                                make_serve_step, translate_step)


def _small_spec(m: HybridKVManager) -> DecodeSpec:
    cfg = m.cfg
    return DecodeSpec(block_size=cfg.block_size,
                      max_blocks_per_seq=cfg.max_blocks_per_seq,
                      slots_per_group=cfg.total_slots,
                      n_sets=cfg.num_sets, assoc=cfg.assoc,
                      hash_name=cfg.hash_name)


def _random_workload(seed: int, n_ops: int = 80) -> HybridKVManager:
    """Drive a manager through a random alloc/free/share/promote history."""
    rng = np.random.RandomState(seed)
    cfg = HybridConfig(total_slots=48, restseg_fraction=0.5, assoc=4,
                       max_seqs=4, max_blocks_per_seq=8,
                       promote_freq_threshold=2, promote_cost_threshold=4)
    m = HybridKVManager(cfg)
    live = []
    for _ in range(n_ops):
        op = rng.randint(6)
        if op == 0 and len(live) < cfg.max_seqs:
            sid = int(rng.randint(1000))
            if sid not in live:
                m.register_sequence(sid)
                live.append(sid)
        elif op in (1, 2) and live:
            m.allocate_block(live[rng.randint(len(live))],
                             int(rng.randint(cfg.max_blocks_per_seq)))
        elif op == 3 and len(live) >= 2:
            src, dst = rng.choice(len(live), 2, replace=False)
            m.share_prefix(live[src], live[dst],
                           1 + int(rng.randint(3)))
        elif op == 4 and live and len(live) > 2 and rng.rand() < 0.3:
            sid = live.pop(rng.randint(len(live)))
            m.free_sequence(sid)
        elif op == 5 and m.blocks:
            vpns = np.array([v for v, i in m.blocks.items()
                             if i.seg != SWAP], np.int64)
            if vpns.size:
                m.record_device_stats(
                    vpns, rng.rand(vpns.size) < 0.5,
                    np.full(vpns.size, 3))
                m.run_promotions()
        m.check_invariants()
    return m


# ------------------------------------------------- translate-once invariant

def test_translation_runs_once_per_step(monkeypatch):
    """The hybrid lookup is dispatched exactly once per step trace.

    The pre-PR decode called the RSW twice at trace time (block-read +
    current-block write) *inside* the layer scan body; the hoisted path
    batches both into one `_hybrid_lookup` call before the scan.  Count
    calls during tracing: must be exactly 1 for a multi-attention-layer
    model.
    """
    cfg = reduced(ARCHS["granite-8b"])
    assert cfg.num_layers >= 2                   # multi-layer, all attention
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    spec = DecodeSpec(block_size=cfg.kv_block_size, max_blocks_per_seq=4,
                      slots_per_group=16, n_sets=2, assoc=4)
    calls = []
    orig = decode_mod._hybrid_lookup

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(decode_mod, "_hybrid_lookup", counting)
    step = make_serve_step(cfg, dims, spec, mesh=None, dtype=jnp.float32)
    dstate = init_decode_state(cfg, dims, spec, 2, 1)
    jax.make_jaxpr(step)(params, dstate, jnp.zeros((2,), jnp.int32))
    assert len(calls) == 1


# ---------------------------------------------- telemetry vs. host oracle

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_step_translation_matches_host_oracle(seed):
    """translate_step (the in-graph telemetry source) == core.translate."""
    m = _random_workload(seed)
    spec = _small_spec(m)
    ts = m.device_state()
    rng = np.random.RandomState(seed + 100)
    B = m.cfg.max_seqs
    positions = jnp.asarray(
        rng.randint(0, m.cfg.max_blocks_per_seq * m.cfg.block_size, B),
        jnp.int32)
    tar = jnp.asarray(m.tar)[None]
    sf = jnp.asarray(m.sf)[None]
    flex = jnp.asarray(m.flex_table.reshape(-1))[None]
    tr = translate_step(tar, sf, flex, positions, spec)

    vpns = np.asarray(tr.vpns).reshape(-1)
    oracle = translate(ts, jnp.asarray(vpns, jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(tr.slots[0]).reshape(-1), np.asarray(oracle.slot))
    np.testing.assert_array_equal(
        np.asarray(tr.in_rest[0]).reshape(-1), np.asarray(oracle.in_rest))
    np.testing.assert_array_equal(
        np.asarray(tr.mapped[0]).reshape(-1), np.asarray(oracle.mapped))
    np.testing.assert_array_equal(
        np.asarray(tr.accesses[0]).reshape(-1), np.asarray(oracle.accesses))
    # the write-slot lookup agrees with the oracle on the current blocks
    cur_vpn = (np.arange(B) * m.cfg.max_blocks_per_seq
               + np.asarray(positions) // m.cfg.block_size)
    w_oracle = translate(ts, jnp.asarray(cur_vpn, jnp.int32))
    np.testing.assert_array_equal(np.asarray(tr.w_valid[0]),
                                  np.asarray(w_oracle.mapped))
    got = np.asarray(tr.w_slot[0])[np.asarray(w_oracle.mapped)]
    np.testing.assert_array_equal(
        got, np.asarray(w_oracle.slot)[np.asarray(w_oracle.mapped)])


def test_stale_slot_write_is_masked():
    """An idle/released slot whose ctx_len ran past its vpn range must
    never produce a valid write slot (its cur_vpn would otherwise alias
    another sequence's blocks and corrupt a live block)."""
    m = _random_workload(0)
    spec = _small_spec(m)
    nblk, bs = m.cfg.max_blocks_per_seq, m.cfg.block_size
    B = m.cfg.max_seqs
    positions = jnp.full((B,), nblk * bs + 3, jnp.int32)   # out of range
    tr = translate_step(jnp.asarray(m.tar)[None], jnp.asarray(m.sf)[None],
                        jnp.asarray(m.flex_table.reshape(-1))[None],
                        positions, spec)
    assert not bool(np.asarray(tr.w_valid).any())


# -------------------------------------------------- engine-level contracts

@pytest.fixture(scope="module")
def small_engine_factory():
    cfg = reduced(ARCHS["granite-8b"])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    def make(**kw):
        kw.setdefault("max_batch", 4)
        kw.setdefault("max_seq_len", 4 * cfg.kv_block_size)
        return Engine(cfg, params, **kw), cfg

    return make


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_delta_sync_bit_identical_to_full_reupload(small_engine_factory,
                                                   seed):
    """Randomized alloc/evict/share/promote; after every delta sync the
    device TAR/SF/flex must equal the manager's host mirrors exactly."""
    eng, cfg = small_engine_factory()
    eng._sync_translation(full=True)             # baseline upload
    m = eng.manager
    rng = np.random.RandomState(seed)
    live = []
    for step in range(60):
        op = rng.randint(6)
        if op == 0 and len(live) < m.cfg.max_seqs:
            sid = int(rng.randint(1000))
            if sid not in live:
                m.register_sequence(sid)
                live.append(sid)
        elif op in (1, 2) and live:
            m.allocate_block(live[rng.randint(len(live))],
                             int(rng.randint(m.cfg.max_blocks_per_seq)))
        elif op == 3 and len(live) >= 2:
            s, d = rng.choice(len(live), 2, replace=False)
            m.share_prefix(live[s], live[d], 1 + int(rng.randint(2)))
        elif op == 4 and len(live) > 1 and rng.rand() < 0.3:
            m.free_sequence(live.pop(rng.randint(len(live))))
        elif op == 5 and m.blocks:
            vpns = np.array([v for v, i in m.blocks.items()
                             if i.seg != SWAP], np.int64)
            if vpns.size:
                m.record_device_stats(vpns,
                                      rng.rand(vpns.size) < 0.4,
                                      np.full(vpns.size, 4))
                m.run_promotions()
        m.take_pending_copies()                  # copies irrelevant here
        if rng.rand() < 0.5:                     # sync at random points
            eng._sync_translation()
            np.testing.assert_array_equal(
                np.asarray(eng.dstate["tar"][0]), m.tar)
            np.testing.assert_array_equal(
                np.asarray(eng.dstate["sf"][0]), m.sf)
            np.testing.assert_array_equal(
                np.asarray(eng.dstate["flex"][0]),
                m.flex_table.reshape(-1))
    eng._sync_translation()
    np.testing.assert_array_equal(np.asarray(eng.dstate["tar"][0]), m.tar)
    np.testing.assert_array_equal(np.asarray(eng.dstate["sf"][0]), m.sf)
    np.testing.assert_array_equal(np.asarray(eng.dstate["flex"][0]),
                                  m.flex_table.reshape(-1))


def test_batched_copies_match_sequential(small_engine_factory):
    """One gather/scatter == sequential per-copy application (chains too)."""
    eng, _ = small_engine_factory()
    shape = eng.dstate["k_pool"].shape
    rng = np.random.RandomState(7)
    kp = rng.randn(*shape).astype(np.float32)
    vp = rng.randn(*shape).astype(np.float32)
    # buffers of the engine's own (``jnp.array`` copies): the pools are
    # donated to the slot-copy program, which must not meet a view of kp
    eng.dstate["k_pool"] = jnp.array(kp)
    eng.dstate["v_pool"] = jnp.array(vp)
    # includes a chain 3->5->9 and an overwrite of dst 11
    copies = [(3, 5), (5, 9), (2, 11), (4, 11), (0, 1)]
    ref_k, ref_v = kp.copy(), vp.copy()
    for s, d in copies:
        ref_k[:, d] = ref_k[:, s]
        ref_v[:, d] = ref_v[:, s]
    eng.manager.pending_copies = list(copies)
    eng._apply_copies()
    np.testing.assert_array_equal(np.asarray(eng.dstate["k_pool"]), ref_k)
    np.testing.assert_array_equal(np.asarray(eng.dstate["v_pool"]), ref_v)


def test_engine_step_single_fetch(small_engine_factory, monkeypatch):
    """The steady-state step performs exactly ONE device->host fetch,
    independent of batch size (two live sequences here)."""
    eng, cfg = small_engine_factory()
    bs = cfg.kv_block_size
    rng = np.random.RandomState(3)
    for sid in (1, 2):
        eng.add_request(Request(seq_id=sid,
                                prompt=rng.randint(0, cfg.vocab_size, bs),
                                max_new_tokens=8))
    fetches = []
    orig = jax.device_get

    def counting(x):
        fetches.append(1)
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    import repro.serve.engine as engine_mod
    monkeypatch.setattr(engine_mod.jax, "device_get", counting)
    for _ in range(3):
        fetches.clear()
        out = eng.step()
        assert len(out) == 2
        assert len(fetches) == 1


def test_spec_decode_step_single_fetch(small_engine_factory, monkeypatch):
    """Speculative decoding preserves the single-fetch contract: one
    device_get per step fetches the whole (B, K+1) accepted window plus
    per-slot emitted counts and telemetry — K+1 tokens per fetch instead
    of one."""
    eng, cfg = small_engine_factory(spec_decode="ngram",
                                    num_draft_tokens=4, max_seq_len=128)
    bs = cfg.kv_block_size
    rng = np.random.RandomState(3)
    for sid in (1, 2):
        eng.add_request(Request(seq_id=sid,
                                prompt=rng.randint(0, cfg.vocab_size, bs),
                                max_new_tokens=64))
    fetches = []
    orig = jax.device_get

    def counting(x):
        fetches.append(1)
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    import repro.serve.engine as engine_mod
    monkeypatch.setattr(engine_mod.jax, "device_get", counting)
    for _ in range(4):
        fetches.clear()
        out = eng.step()
        assert len(out) == 2
        assert len(fetches) == 1


def test_spec_translation_runs_once_per_step(monkeypatch):
    """The speculative verify step dispatches the hybrid lookup exactly
    once: the K+1 per-position write slots are GATHERED from the one
    translation, never re-looked-up."""
    from repro.serve.spec_decode import make_spec_decode_step
    cfg = reduced(ARCHS["granite-8b"])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    spec = DecodeSpec(block_size=cfg.kv_block_size, max_blocks_per_seq=4,
                      slots_per_group=16, n_sets=2, assoc=4)
    calls = []
    orig = decode_mod._hybrid_lookup

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(decode_mod, "_hybrid_lookup", counting)
    step = make_spec_decode_step(cfg, dims, spec, num_draft_tokens=4,
                                 mesh=None, dtype=jnp.float32)
    dstate = init_decode_state(cfg, dims, spec, 2, 1)
    dstate["hist"] = jnp.full((2, 4 * cfg.kv_block_size), -1, jnp.int32)
    jax.make_jaxpr(step)(params, dstate, jnp.zeros((2,), jnp.int32))
    assert len(calls) == 1
