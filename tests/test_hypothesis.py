"""Property-based tests (hypothesis) on the system's invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

# hypothesis is an optional dev dependency: skip (not error) when absent
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.core import (HybridConfig, HybridKVManager, get_hash, HASHES,
                        translate, REST, FLEX, SWAP)
from repro.core.policies import SRRIP
from repro.dist import compression
from repro.kernels.utopia_rsw.ref import rsw_ref
from repro.kernels.utopia_rsw.ops import utopia_rsw

SETTINGS = dict(deadline=None, max_examples=25,
                suppress_health_check=[HealthCheck.too_slow])


@st.composite
def manager_and_ops(draw):
    assoc = draw(st.sampled_from([2, 4, 8]))
    n_sets = draw(st.sampled_from([2, 4, 8]))
    flex = draw(st.integers(4, 32))
    total = n_sets * assoc + flex
    max_seqs = draw(st.integers(2, 6))
    max_blocks = draw(st.sampled_from([8, 16]))
    hash_name = draw(st.sampled_from(sorted(HASHES)))
    cfg = HybridConfig(total_slots=total,
                       restseg_fraction=n_sets * assoc / total,
                       assoc=assoc, max_seqs=max_seqs,
                       max_blocks_per_seq=max_blocks, hash_name=hash_name)
    n_ops = draw(st.integers(5, 60))
    ops = [draw(st.tuples(st.sampled_from(["reg", "alloc", "free", "share",
                                           "stats"]),
                          st.integers(0, max_seqs - 1),
                          st.integers(0, max_blocks - 1)))
           for _ in range(n_ops)]
    return cfg, ops


@given(manager_and_ops())
@settings(**SETTINGS)
def test_manager_invariants_hold_under_any_op_sequence(case):
    """SF == TAR occupancy; TAR tags match block registry; no slot is both
    mapped and free — after any sequence of operations."""
    cfg, ops = case
    m = HybridKVManager(cfg)
    live = set()
    for op, s, b in ops:
        try:
            if op == "reg" and len(live) < cfg.max_seqs:
                m.register_sequence(s)
                live.add(s)
            elif op == "alloc" and s in live:
                m.allocate_block(s, b)
            elif op == "free" and s in live:
                m.free_sequence(s)
                live.discard(s)
            elif op == "share" and s in live and ((s + 1) % cfg.max_seqs) in live:
                m.share_prefix(s, (s + 1) % cfg.max_seqs, 1 + b % 4)
            elif op == "stats" and s in live:
                vpns = np.array([m.cfg.vpn(m.seq_slot(s), bb)
                                 for bb in range(4)])
                vpns = np.array([v for v in vpns if v in m.blocks])
                if vpns.size:
                    m.record_device_stats(
                        vpns, np.zeros(len(vpns), bool),
                        np.full(len(vpns), 4))
                    m.run_promotions()
        except Exception as e:  # only PoolExhausted-ish errors are legal
            from repro.core import PoolExhausted
            assert isinstance(e, (PoolExhausted, KeyError, ValueError)), e
        m.check_invariants()


@given(manager_and_ops())
@settings(**SETTINGS)
def test_translation_total_and_exclusive(case):
    """Every allocated block translates to exactly one segment, and device
    translation agrees with the host registry."""
    cfg, ops = case
    m = HybridKVManager(cfg)
    live = set()
    for op, s, b in ops:
        if op == "reg" and len(live) < cfg.max_seqs:
            m.register_sequence(s)
            live.add(s)
        elif op == "alloc" and s in live:
            m.allocate_block(s, b)
    ts = m.device_state()
    for vpn, info in m.blocks.items():
        res = translate(ts, jnp.array([vpn], jnp.int32))
        if info.seg == SWAP:
            assert not bool(res.mapped[0])
        else:
            assert bool(res.mapped[0])
            assert int(res.slot[0]) == info.slot
            assert bool(res.in_rest[0]) == (info.seg == REST)


@given(st.integers(0, 2**27), st.sampled_from(sorted(HASHES)),
       st.sampled_from([4, 8, 96, 128, 480]))
@settings(**SETTINGS)
def test_hash_domain_consistency(vpn, name, n_sets):
    h = get_hash(name)
    a = h(vpn, n_sets)
    b = int(np.asarray(h(np.array([vpn], np.int32), n_sets))[0])
    c = int(np.asarray(h(jnp.array([vpn], jnp.int32), n_sets))[0])
    assert a == b == c
    assert 0 <= a < n_sets


@given(st.lists(st.integers(0, 511), min_size=1, max_size=64, unique=True))
@settings(**SETTINGS)
def test_rsw_kernel_equals_ref_on_random_tables(vpns):
    rng = np.random.RandomState(sum(vpns) % 2**31)
    n_sets, assoc = 16, 4
    tar = np.zeros((n_sets, assoc), np.int32)
    # install a random subset at their correct sets
    for v in rng.choice(512, size=40, replace=False):
        s = v % n_sets
        ways = np.nonzero(tar[s] == 0)[0]
        if ways.size:
            tar[s, ways[0]] = v + 1
    sf = (tar != 0).sum(axis=1).astype(np.int32)
    flex = rng.randint(-1, 64, size=512).astype(np.int32)
    out_k = utopia_rsw(jnp.asarray(vpns, jnp.int32), jnp.asarray(tar),
                       jnp.asarray(sf), jnp.asarray(flex), interpret=True)
    out_r = rsw_ref(jnp.asarray(vpns, jnp.int32), jnp.asarray(tar),
                    jnp.asarray(sf), jnp.asarray(flex))
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 7), st.integers(2, 4))
@settings(**SETTINGS)
def test_srrip_victim_always_valid(seed, assoc):
    rng = np.random.RandomState(seed)
    srrip = SRRIP(4, assoc)
    valid = rng.rand(assoc) > 0.3
    if not valid.any():
        valid[0] = True
    v = srrip.victim(0, valid)
    assert valid[v]


@given(st.lists(st.floats(-10, 10, allow_nan=False), min_size=4,
                max_size=64))
@settings(**SETTINGS)
def test_ef_compression_residual_bound(xs):
    """Quantization error never exceeds half a quantization step, and the
    error-feedback identity sum(g_hat) + residual == sum(g) holds."""
    g = jnp.asarray(np.array(xs, np.float32))
    ef = compression.EFState(residual=jnp.zeros_like(g))
    g_hat, ef2 = compression.compress_with_ef(g, ef)
    np.testing.assert_allclose(np.asarray(g_hat + ef2.residual),
                               np.asarray(g), rtol=1e-5, atol=1e-5)
