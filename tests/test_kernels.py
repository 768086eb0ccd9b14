"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (assignment requirement)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

from repro.core import HybridConfig, HybridKVManager, translate
from repro.kernels.utopia_rsw.ops import utopia_rsw
from repro.kernels.utopia_rsw.ref import rsw_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref, normalize
from repro.models.attention import dense_attention


def _populated_manager(hash_name="modulo", seqs=6, blocks=20):
    cfg = HybridConfig(total_slots=256, restseg_fraction=0.75, assoc=8,
                       max_seqs=16, max_blocks_per_seq=32,
                       hash_name=hash_name)
    m = HybridKVManager(cfg)
    for sid in range(seqs):
        m.register_sequence(sid)
        for b in range(blocks):
            m.allocate_block(sid, b)
    return m


class TestRSWKernel:
    @pytest.mark.parametrize("hash_name", ["modulo", "xor_fold",
                                           "prime_displacement", "mersenne",
                                           "multiplicative"])
    def test_matches_ref_and_core(self, hash_name):
        m = _populated_manager(hash_name)
        ts = m.device_state()
        ff = ts.flex.table.reshape(-1)
        vpns = jnp.arange(16 * 32, dtype=jnp.int32)
        got = utopia_rsw(vpns, ts.rest.tar, ts.rest.sf, ff,
                         hash_name=hash_name, interpret=True)
        want = rsw_ref(vpns, ts.rest.tar, ts.rest.sf, ff,
                       hash_name=hash_name)
        for a, b in zip(got, want):
            npt.assert_array_equal(np.asarray(a), np.asarray(b))
        tr = translate(ts, vpns)
        npt.assert_array_equal(
            np.asarray(got[0]),
            np.where(np.asarray(tr.mapped), np.asarray(tr.slot), -1))

    @pytest.mark.parametrize("tile", [32, 128, 256])
    def test_tile_sizes_and_padding(self, tile):
        m = _populated_manager()
        ts = m.device_state()
        ff = ts.flex.table.reshape(-1)
        vpns = jnp.arange(100, dtype=jnp.int32)   # not a tile multiple
        got = utopia_rsw(vpns, ts.rest.tar, ts.rest.sf, ff, tile=tile,
                         interpret=True)
        want = rsw_ref(vpns, ts.rest.tar, ts.rest.sf, ff)
        for a, b in zip(got, want):
            npt.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_large_vpn_tags_exact(self):
        """Tags (vpn+1) at and above 2^24 must match exactly.

        A float32 one-hot matmul rounds odd tags ≥ 2^24 to the nearest
        even value, so vpn=2^24 (tag 2^24+1) silently missed — and worse,
        a query for a *different* vpn whose tag rounds onto an installed
        one falsely hit.  The kernel now recombines 16-bit tag halves in
        int32; this pins both directions against the oracle.
        """
        n_sets, assoc = 4, 4
        tar = np.zeros((n_sets, assoc), np.int32)
        big = [(1 << 24), (1 << 24) + 6, (1 << 25) + 3, (1 << 26) + 9]
        for v in big:
            s = v % n_sets
            way = int(np.nonzero(tar[s] == 0)[0][0])
            tar[s, way] = v + 1                       # odd tags ≥ 2^24
        sf = (tar != 0).sum(axis=1).astype(np.int32)
        flex = -np.ones(16, np.int32)
        # installed vpns, near-miss neighbours (tags that round onto the
        # installed ones in f32), and small controls
        queries = big + [v + 1 for v in big] + [v - 1 for v in big] + [0, 7]
        vpns = jnp.asarray(queries, jnp.int32)
        got = utopia_rsw(vpns, jnp.asarray(tar), jnp.asarray(sf),
                         jnp.asarray(flex), interpret=True)
        want = rsw_ref(vpns, jnp.asarray(tar), jnp.asarray(sf),
                       jnp.asarray(flex))
        for a, b in zip(got, want):
            npt.assert_array_equal(np.asarray(a), np.asarray(b))
        # installed vpns hit the RestSeg; their neighbours must not
        n = len(big)
        assert np.asarray(got[1][:n]).all(), "installed vpns must RSW-hit"
        assert not np.asarray(got[1][n:3 * n]).any(), \
            "rounded-tag neighbours must miss"

    def test_out_of_range_vpn_unmapped(self):
        """A vpn past the end of the flex table reads as unmapped in both
        the kernel and the oracle, even when the table's last entry is
        mapped (a clamped gather would return it)."""
        m = _populated_manager()
        ts = m.device_state()
        ff = ts.flex.table.reshape(-1).at[-1].set(7)
        n = ff.shape[0]
        vpns = jnp.asarray([0, n - 1, n, n + 5, 4 * n], jnp.int32)
        got = utopia_rsw(vpns, ts.rest.tar, ts.rest.sf, ff, interpret=True)
        want = rsw_ref(vpns, ts.rest.tar, ts.rest.sf, ff)
        for a, b in zip(got, want):
            npt.assert_array_equal(np.asarray(a), np.asarray(b))
        hit = np.asarray(got[1]).astype(bool)
        assert not hit[1] and int(got[0][1]) == 7
        assert (np.asarray(got[0])[2:][~hit[2:]] == -1).all()

    def test_host_agreement(self):
        m = _populated_manager()
        ts = m.device_state()
        ff = ts.flex.table.reshape(-1)
        for sid in range(6):
            for b in range(20):
                vpn = m.cfg.vpn(m.seq_slot(sid), b)
                got = utopia_rsw(jnp.array([vpn], jnp.int32), ts.rest.tar,
                                 ts.rest.sf, ff, interpret=True)
                assert int(got[0][0]) == m.lookup(sid, b)[0]


class TestFlashKernel:
    @pytest.mark.parametrize("shape", [
        (2, 128, 4, 2, 32), (1, 256, 8, 8, 16), (2, 64, 4, 1, 64),
        (1, 128, 6, 3, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_dense(self, shape, dtype, causal):
        B, S, H, KV, D = shape
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), dtype)
        k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
        v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
        out = flash_attention(q, k, v, causal=causal, q_tile=64, kv_tile=64,
                              interpret=True)
        ref = dense_attention(q, k, v, causal=causal)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        npt.assert_allclose(np.asarray(out, np.float32),
                            np.asarray(ref, np.float32), rtol=tol, atol=tol)


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("shape", [
        (3, 8, 2, 32, 16, 6, 64), (2, 4, 4, 16, 8, 4, 32),
        (1, 8, 1, 64, 32, 8, 96),
    ])
    def test_vs_ref_with_holes(self, shape):
        B, H, KV, D, bs, nblk, nslots = shape
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        slots = slots.at[0, nblk // 2].set(-1)          # hole
        ctx = jnp.asarray(np.random.RandomState(0).randint(
            1, bs * nblk, B), jnp.int32)
        out_k = paged_attention(q, kp, vp, slots, ctx, use_kernel=True,
                                interpret=True)
        o, m, l = paged_attention_ref(q, kp, vp, slots, ctx)
        npt.assert_allclose(np.asarray(out_k), np.asarray(normalize(o, l)),
                            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("shape", [
        # B, Q, H, KV, D, bs, nblk, nslots
        (2, 4, 8, 2, 32, 16, 6, 64), (3, 1, 4, 4, 16, 8, 4, 32),
        (1, 16, 8, 1, 64, 32, 8, 96),
    ])
    def test_multi_token_query_vs_ref(self, shape):
        """Q>1 queries (prefix-KV chunked prefill): Pallas-interpret vs
        the jnp oracle over ragged ctx_len — block-interior, exact block
        boundaries, and an EMPTY-prefix row (ctx 0, l == 0 so the
        flash-decoding combine drops the part exactly) — plus a hole."""
        B, Q, H, KV, D, bs, nblk, nslots = shape
        ks = jax.random.split(jax.random.PRNGKey(11), 4)
        q = jax.random.normal(ks[0], (B, Q, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        slots = slots.at[0, nblk // 2].set(-1)          # hole
        ctx = np.random.RandomState(1).randint(1, bs * nblk, B)
        ctx[0] = 0                                      # empty prefix
        if B > 1:
            ctx[1] = bs * (nblk // 2)                   # block boundary
        ctx = jnp.asarray(ctx, jnp.int32)
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        got = paged_attention_pallas(q, kp, vp, slots, ctx, interpret=True)
        want = paged_attention_ref(q, kp, vp, slots, ctx)
        for a, b in zip(got, want):
            assert a.shape == b.shape == (B, Q, H) + ((D,) if a.ndim == 4
                                                      else ())
            npt.assert_allclose(np.asarray(a), np.asarray(b),
                                rtol=2e-5, atol=2e-5)
        # empty-prefix row: zero weight everywhere, so normalize -> 0
        npt.assert_array_equal(np.asarray(normalize(got[0], got[2]))[0], 0.0)

    @pytest.mark.parametrize("K", [1, 3, 4])
    @pytest.mark.parametrize("pattern", ["ragged", "boundary",
                                         "all_rejected", "all_accepted"])
    def test_verify_shaped_per_query_ctx_vs_ref(self, K, pattern):
        """Speculative-verify shapes: Q = K+1 queries with PER-QUERY
        context extents ctx_q[b, i] = pos_b + i + 1 (the sequential
        causal mask inside one pool read).  Pallas-interpret must match
        the jnp oracle, and each query column must equal an independent
        single-extent call — the property that makes spec-on greedy
        streams token-identical to sequential decode.

        Patterns: ``ragged`` starts rows mid-block, ``boundary`` starts
        exactly at a block boundary so the window straddles it,
        ``all_rejected`` re-verifies from the same base every row (the
        worst case: next step's window repeats the position), and
        ``all_accepted`` chains two adjacent windows (row 1 starts where
        row 0's window committed)."""
        B, H, KV, D, bs, nblk, nslots = 2, 4, 2, 16, 8, 6, 64
        Q = K + 1
        ks = jax.random.split(jax.random.PRNGKey(21 + K), 4)
        q = jax.random.normal(ks[0], (B, Q, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        base = {
            "ragged": np.array([bs - 2, 3 * bs - 1]),   # straddles blocks
            "boundary": np.array([bs, 2 * bs]),
            "all_rejected": np.array([7, 7]),
            "all_accepted": np.array([5, 5 + Q]),
        }[pattern]
        ctx_q = jnp.asarray(base[:, None] + 1 + np.arange(Q)[None, :],
                            jnp.int32)
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        got = paged_attention_pallas(q, kp, vp, slots, ctx_q,
                                     interpret=True)
        want = paged_attention_ref(q, kp, vp, slots, ctx_q)
        for a, b in zip(got, want):
            npt.assert_allclose(np.asarray(a), np.asarray(b),
                                rtol=2e-5, atol=2e-5)
        # column i == an independent single-extent call (bitwise, for the
        # ref path: the verify step's token-identity foundation)
        for i in range(Q):
            o1, m1, l1 = paged_attention_ref(q[:, i], kp, vp, slots,
                                             ctx_q[:, i])
            npt.assert_array_equal(np.asarray(o1),
                                   np.asarray(want[0][:, i]))
            npt.assert_array_equal(np.asarray(m1),
                                   np.asarray(want[1][:, i]))
            npt.assert_array_equal(np.asarray(l1),
                                   np.asarray(want[2][:, i]))

    @pytest.mark.parametrize("Q", [12, 76])
    @pytest.mark.parametrize("per_query", [False, True],
                             ids=["row_extent", "per_query_extent"])
    def test_query_tiles_with_padding_vs_ref(self, per_query, Q):
        """At 32 heads the kernel scores queries 8 at a time and puts up
        to 64 in one query block: Q = 12 runs two tiles in one block, the
        second padded; Q = 76 runs two blocks of five tiles.  Both extent
        forms must match the oracle on every real query."""
        B, H, KV, D, bs, nblk, nslots = 2, 32, 8, 16, 8, 4, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, Q, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        base = np.array([5, bs * 2])
        ctx = (base[:, None] + 1 + np.arange(Q)[None, :]) if per_query \
            else base
        ctx = jnp.asarray(ctx, jnp.int32)
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        got = paged_attention_pallas(q, kp, vp, slots, ctx, interpret=True)
        want = paged_attention_ref(q, kp, vp, slots, ctx)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            npt.assert_allclose(np.asarray(a), np.asarray(b),
                                rtol=2e-5, atol=2e-5)

    def test_per_query_ctx_zero_extent_column_drops(self):
        """A query column with extent 0 yields l == 0 (dropped exactly by
        any downstream flash-decoding combine)."""
        B, Q, H, KV, D, bs, nblk, nslots = 1, 3, 4, 2, 16, 8, 4, 32
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q = jax.random.normal(ks[0], (B, Q, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        ctx_q = jnp.asarray([[0, 5, 9]], jnp.int32)
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        pallas = functools.partial(paged_attention_pallas, interpret=True)
        for fn in (paged_attention_ref, pallas):
            o, m, l = fn(q, kp, vp, slots, ctx_q)
            npt.assert_array_equal(np.asarray(l)[:, 0], 0.0)
            npt.assert_array_equal(
                np.asarray(normalize(o, l))[:, 0], 0.0)
            assert (np.asarray(l)[:, 1:] > 0).all()

    def test_q1_query_rank_round_trip(self):
        """A (B,H,D) decode query and its (B,1,H,D) chunk form produce
        identical results in BOTH implementations (one code path, two
        ranks)."""
        B, H, KV, D, bs, nblk, nslots = 2, 4, 2, 16, 8, 4, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 4)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        ctx = jnp.asarray([13, 27], jnp.int32)
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention_pallas)
        pallas = functools.partial(paged_attention_pallas, interpret=True)
        for fn in (paged_attention_ref, pallas):
            o3, m3, l3 = fn(q, kp, vp, slots, ctx)
            o4, m4, l4 = fn(q[:, None], kp, vp, slots, ctx)
            npt.assert_array_equal(np.asarray(o3), np.asarray(o4[:, 0]))
            npt.assert_array_equal(np.asarray(m3), np.asarray(m4[:, 0]))
            npt.assert_array_equal(np.asarray(l3), np.asarray(l4[:, 0]))

    def test_multi_token_prefix_plus_chunk_merge_matches_dense(self):
        """End-to-end prefix-KV attention identity: Q chunk queries over
        [pool prefix] ∪ [own causal K/V], combined with the online-softmax
        merge, equals ONE dense causal attention over the concatenated
        sequence."""
        from repro.models.attention import (dense_attention,
                                            causal_attention_parts,
                                            merge_attention_parts)
        B, Q, H, KV, D, bs, nblk, nslots = 2, 8, 4, 2, 16, 8, 4, 32
        P = bs * nblk                                   # prefix tokens
        ks = jax.random.split(jax.random.PRNGKey(9), 6)
        q = jax.random.normal(ks[0], (B, Q, H, D))
        kpre = jax.random.normal(ks[1], (B, P, KV, D))
        vpre = jax.random.normal(ks[2], (B, P, KV, D))
        kc = jax.random.normal(ks[3], (B, Q, KV, D))
        vc = jax.random.normal(ks[4], (B, Q, KV, D))
        # lay the prefix into pool slots (row b uses slots b*nblk + j)
        kp = jnp.zeros((nslots, bs, KV, D)).at[:2 * nblk].set(
            kpre.reshape(B * nblk, bs, KV, D))
        vp = jnp.zeros((nslots, bs, KV, D)).at[:2 * nblk].set(
            vpre.reshape(B * nblk, bs, KV, D))
        slots = (jnp.arange(B)[:, None] * nblk
                 + jnp.arange(nblk)[None, :]).astype(jnp.int32)
        ctx = jnp.full((B,), P, jnp.int32)
        pool = paged_attention_ref(q, kp, vp, slots, ctx)
        own = causal_attention_parts(q, kc, vc)
        merged = merge_attention_parts([pool, own])
        dense = dense_attention(
            q, jnp.concatenate([kpre, kc], axis=1),
            jnp.concatenate([vpre, vc], axis=1), causal=True, q_offset=P)
        npt.assert_allclose(np.asarray(merged), np.asarray(dense),
                            rtol=2e-5, atol=2e-5)

    def test_striped_token_shards_combine(self):
        """Model-axis token striping: shard partials must combine exactly."""
        B, H, KV, D, bs, nblk, nslots = 2, 4, 2, 16, 16, 4, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (nslots, bs, KV, D))
        vp = jax.random.normal(ks[2], (nslots, bs, KV, D))
        slots = jax.random.randint(ks[3], (B, nblk), 0, nslots)
        ctx = jnp.array([60, 37], jnp.int32)
        o, m, l = paged_attention_ref(q, kp, vp, slots, ctx)
        full = np.asarray(normalize(o, l))
        TP = 4
        outs = []
        for t in range(TP):
            lo = t * (bs // TP)
            kp_t = kp[:, lo:lo + bs // TP]
            vp_t = vp[:, lo:lo + bs // TP]
            outs.append(paged_attention_ref(
                q, kp_t, vp_t, slots, ctx, tok_offset=lo, tok_stride=1,
                block_tokens=bs))
        m_glob = np.max([np.asarray(x[1]) for x in outs], axis=0)
        o_sum = sum(np.asarray(x[0]) * np.exp(np.asarray(x[1]) - m_glob)
                    [..., None] for x in outs)
        l_sum = sum(np.asarray(x[2]) * np.exp(np.asarray(x[1]) - m_glob)
                    for x in outs)
        npt.assert_allclose(o_sum / np.maximum(l_sum, 1e-30)[..., None],
                            full, rtol=2e-5, atol=2e-5)
