"""The engine donates its decode state to every step program.

``Engine`` hands ``self.dstate`` to the serve, prefill, prefix-KV and
speculative steps as a donated argument (``donate_argnums=(1,)``): each
program's outputs alias its input buffers, so a step updates the K/V
pools in place instead of first copying both pools into fresh outputs.
Slot migrations and resume scatters go through one jitted program that
donates the pools too (``engine._write_pool_slots``).

* Consumption: after each program call the pre-call pools are deleted,
  and the pool's device buffer is the one the step wrote into (same
  address before and after), on a dense, a speculative and a hybrid
  (jamba) engine, with no "donated buffers were not usable" warning.
* Structure: the compiled serve, prefill and prefix steps alias both
  pools to outputs (``input_output_alias``) and their entry computation
  copies no pool; with the donation taken away (the engine before this
  change) the same check finds both copies.
* Bitwise oracle: served tokens and the final pools equal those of an
  engine built without donation, over a run with prefix-cache pins
  (rest->flex migrations through ``_apply_copies``), forced
  preempt/resume and a snapshot/restore round trip.
* The same consumption on a (1, 2) mesh, in a subprocess with two CPU
  devices.
"""
import contextlib
import dataclasses
import os
import re
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import init_params, model_dims
from repro.runtime import ServeFaultInjector
from repro.serve import Engine, EngineConfig, Request
from repro.serve import engine as engine_mod

PROGRAMS = {"_serve_step": "serve", "_prefill_step": "prefill",
            "_prefix_step": "prefix", "_spec_step": "spec"}
POOLS = ("k_pool", "v_pool")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SETUP = {}


def _setup(arch):
    """2-layer reduced model: several engines run here."""
    if arch not in _SETUP:
        cfg = dataclasses.replace(reduced(ARCHS[arch]), num_layers=2)
        params = init_params(jax.random.PRNGKey(2), cfg,
                             model_dims(cfg, tp=1))
        _SETUP[arch] = (cfg, params)
    return _SETUP[arch]


VARIANTS = {
    "dense": ("granite-8b", {"prefix_cache": "auto"}),
    "spec": ("granite-8b", {"prefix_cache": "auto", "spec_decode": "ngram",
                            "num_draft_tokens": 3}),
    "hybrid": ("jamba-1.5-large-398b", {}),
}


def _engine(variant, injector=None):
    arch, kw = VARIANTS[variant]
    cfg, params = _setup(arch)
    bs = cfg.kv_block_size
    # one block of prompt a step: the first chunk runs the prefill
    # program, later chunks the prefix-KV program
    return Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=8 * bs, prefill_budget=bs,
        auto_release=True, fault_injector=injector, **kw))


def _submit(eng, n=5, max_new=6):
    """Requests that share a two-block prefix, then differ."""
    cfg = eng.cfg
    bs = cfg.kv_block_size
    rng = np.random.RandomState(5)
    shared = rng.randint(0, cfg.vocab_size, 2 * bs)
    for i in range(n):
        eng.submit(Request(
            seq_id=i, prompt=np.concatenate(
                [shared, rng.randint(0, cfg.vocab_size, bs)]),
            max_new_tokens=max_new))


def _ptr(a):
    return [s.data.unsafe_buffer_pointer() for s in a.addressable_shards]


def _abstract(tree):
    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
                   if isinstance(a, jax.Array) else a), tree)


def _watch(eng):
    """Wrap the engine's step programs.  Per program: the jitted
    callable, the abstract arguments of its first call, and for every
    call whether it deleted the pre-call pools and wrote its output
    pools into their buffers."""
    seen = {}
    for attr, kind in PROGRAMS.items():
        fn = getattr(eng, attr, None)
        if fn is None:
            continue

        def run(*a, _fn=fn, _kind=kind, **k):
            pools = [a[1][p] for p in POOLS]
            rec = seen.setdefault(_kind, {"jit": _fn, "deleted": [],
                                          "in_place": [],
                                          "args": _abstract((a, k))})
            before = [_ptr(p) for p in pools]
            out = _fn(*a, **k)
            rec["deleted"].append(all(p.is_deleted() for p in pools))
            rec["in_place"].append(
                before == [_ptr(out[1][p]) for p in POOLS])
            return out

        setattr(eng, attr, run)
    return seen


def _run_watched(variant):
    eng = _engine(variant)
    seen = _watch(eng)
    _submit(eng)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        steps = []
        while eng.has_unfinished():
            old = [eng.dstate[p] for p in POOLS]
            eng.step()
            steps.append(all(p.is_deleted() for p in old))
    return eng, seen, steps, [str(w.message) for w in caught]


_RUNS = {}


def _watched(variant):
    if variant not in _RUNS:
        _RUNS[variant] = _run_watched(variant)
    return _RUNS[variant]


EXPECTED = {"dense": {"serve", "prefill", "prefix"},
            "spec": {"spec", "prefill", "prefix"},
            "hybrid": {"serve", "prefill", "prefix"}}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_step_programs_consume_the_pre_step_pools(variant):
    eng, seen, steps, caught = _watched(variant)
    assert set(seen) == EXPECTED[variant]
    for kind, rec in seen.items():
        assert rec["deleted"] and all(rec["deleted"]), kind
        assert all(rec["in_place"]), (kind, rec["in_place"])
    # every engine step ran at least one program: the pools it started
    # with are gone after it
    assert steps and all(steps)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_no_donated_buffer_goes_unused(variant):
    _, _, _, caught = _watched(variant)
    assert not [m for m in caught if "donated buffers" in m], caught


# ------------------------------------------- compiled aliasing

def _pool_aliasing(jitted, args):
    """(pools aliased to an output, pool-shaped copies in the entry
    computation) of ``jitted`` compiled for ``args``."""
    a, k = args
    txt = jitted.lower(*a, **k).compile().as_text()
    header = txt.splitlines()[0]
    aliased = {int(p) for p in
               re.findall(r"\{[\d,\s]*\}: \((\d+), \{\}", header)}
    entry = txt[txt.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    params = dict(re.findall(r"%\S*(k_pool|v_pool)\S* = (?:\S+) "
                             r"parameter\((\d+)\)", entry))
    assert set(params) == set(POOLS), params
    shape = ",".join(map(str, a[1]["k_pool"].shape))
    copies = re.findall(r"= \w+\[" + shape + r"\]\{[^}]*\} copy\(", entry)
    return {p for p, i in params.items() if int(i) in aliased}, len(copies)


@pytest.mark.parametrize("kind", ["serve", "prefill", "prefix"])
def test_compiled_steps_alias_the_pools(kind):
    _, seen, _, _ = _watched("dense")
    aliased, copies = _pool_aliasing(seen[kind]["jit"], seen[kind]["args"])
    assert aliased == set(POOLS)
    assert copies == 0


@contextlib.contextmanager
def _without_donation():
    """Build engines as they were before donation: no step program
    donates, pool writes run eagerly."""
    jit = jax.jit

    def plain(fn, **kw):
        kw.pop("donate_argnums", None)
        return jit(fn, **kw)

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "jit", plain)
    mp.setattr(engine_mod, "_write_pool_slots",
               engine_mod._write_pool_slots.__wrapped__)
    try:
        yield
    finally:
        mp.undo()


@pytest.mark.parametrize("kind", ["serve", "prefill", "prefix"])
def test_undonated_steps_copy_the_pools(kind):
    """The structural check above fails on an engine without donation:
    nothing is aliased and both pools are copied."""
    with _without_donation():
        eng = _engine("dense")
    seen = _watch(eng)
    _submit(eng, n=2, max_new=2)
    while eng.has_unfinished():
        eng.step()
    assert not any(seen[kind]["deleted"])
    aliased, copies = _pool_aliasing(seen[kind]["jit"], seen[kind]["args"])
    assert aliased == set()
    assert copies == 2


# ------------------------------------------- pool writes

def test_write_pool_writes_slots_in_place_and_counts():
    eng = _engine("dense")
    rng = np.random.RandomState(0)
    shape = eng.dstate["k_pool"].shape
    kp = rng.randn(*shape).astype(np.float32)
    vp = rng.randn(*shape).astype(np.float32)
    eng.dstate["k_pool"] = jnp.array(kp)
    eng.dstate["v_pool"] = jnp.array(vp)
    before = _ptr(eng.dstate["k_pool"])
    # three slots: padded to four by repeating the first
    dst = np.asarray([7, 2, 11], np.int32)
    kv = rng.randn(2, shape[0], 3, *shape[2:]).astype(np.float32)
    eng._write_pool(dst, kv=kv)
    kp[:, dst], vp[:, dst] = kv[0], kv[1]
    np.testing.assert_array_equal(np.asarray(eng.dstate["k_pool"]), kp)
    np.testing.assert_array_equal(np.asarray(eng.dstate["v_pool"]), vp)
    assert _ptr(eng.dstate["k_pool"]) == before
    eng._write_pool(np.asarray([1], np.int32),
                    src=np.asarray([7], np.int32))
    kp[:, 1], vp[:, 1] = kp[:, 7], vp[:, 7]
    np.testing.assert_array_equal(np.asarray(eng.dstate["k_pool"]), kp)
    np.testing.assert_array_equal(np.asarray(eng.dstate["v_pool"]), vp)
    assert _ptr(eng.dstate["k_pool"]) == before
    assert eng.stats()["pool_writes"] == {"dispatches": 2, "slots": 4}


# ------------------------------------------- bitwise oracle

def _serve(variant, snap_at=4):
    """Serve under forced preemptions, with a snapshot/restore round
    trip at step ``snap_at``; returns (streams, final pools, stats)."""
    inj = ServeFaultInjector(preempt_at=[(3, "pre", "auto"),
                                         (6, "post", "auto"),
                                         (9, "pre", "auto")])
    eng = _engine(variant, injector=inj)
    _submit(eng, n=6, max_new=8)
    outs = {}
    n = 0
    while eng.has_unfinished():
        for ro in eng.poll():
            outs.setdefault(ro.seq_id, []).extend(ro.new_token_ids)
        n += 1
        if n == snap_at:
            eng.restore(eng.snapshot())
        eng.manager.check_invariants()
        if eng.prefix_cache is not None:
            eng.prefix_cache.check_invariants()
    pools = {p: np.asarray(eng.dstate[p]) for p in POOLS}
    return outs, pools, eng.stats()


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_served_tokens_and_pools_match_the_undonated_engine(variant):
    with _without_donation():
        want, want_pools, _ = _serve(variant)
    got, got_pools, stats = _serve(variant)
    assert got == want
    for p in POOLS:
        a, b = got_pools[p], want_pools[p]
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(f"u{a.dtype.itemsize}"),
                                      b.view(f"u{b.dtype.itemsize}"),
                                      err_msg=p)
    # the run covered what it is meant to cover
    assert stats["overload"]["request_resumes"] >= 1
    assert stats["pool_writes"]["dispatches"] >= 1
    if VARIANTS[variant][1].get("prefix_cache"):
        assert stats["prefix_cache"]["hits"] > 0
        assert stats["migrations_rest_to_flex"] > 0


def _aligned(a, to=64):
    """A copy of ``a`` whose data starts on a ``to``-byte boundary: the
    CPU client puts such an array on the device without copying it."""
    raw = np.empty(a.nbytes + to, np.uint8)
    off = (-raw.ctypes.data) % to
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def _stepped_engine():
    eng = _engine("dense")
    _submit(eng, n=2, max_new=6)
    for _ in range(3):
        eng.step()
    return eng


def _step_in_place(eng):
    """Step; True where both pools were written into their buffers."""
    before = [_ptr(eng.dstate[p]) for p in POOLS]
    old = [eng.dstate[p] for p in POOLS]
    eng.step()
    assert all(p.is_deleted() for p in old)
    return before == [_ptr(eng.dstate[p]) for p in POOLS]


def test_snapshot_holds_no_view_of_the_engine_state():
    """A snapshot owns its arrays: the next step still writes the pools
    in place (a view of a device buffer would make the donation copy),
    and the snapshot stays what it was."""
    eng = _stepped_engine()
    snap = eng.snapshot()
    held = {k: v.copy() for k, v in snap.dstate.items()}
    assert _step_in_place(eng)
    for k, v in snap.dstate.items():
        np.testing.assert_array_equal(v, held[k], err_msg=k)


def test_restore_hands_the_engine_buffers_it_owns():
    """``restore`` puts device buffers of its own in the engine, not
    zero-copy views of the snapshot's arrays, so the next step writes
    its pools into them in place and the snapshot stays a value."""
    eng = _stepped_engine()
    snap = eng.snapshot()
    snap = dataclasses.replace(
        snap, dstate={k: _aligned(v) for k, v in snap.dstate.items()})
    held = {k: v.copy() for k, v in snap.dstate.items()}
    eng.restore(snap)
    for k, v in snap.dstate.items():
        assert v.ctypes.data not in _ptr(eng.dstate[k]), k
    assert _step_in_place(eng)
    for k, v in snap.dstate.items():
        np.testing.assert_array_equal(v, held[k], err_msg=k)


# ------------------------------------------- the sharded step programs

_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import dataclasses, warnings
    import numpy as np, jax
    from repro.configs import ARCHS, reduced
    from repro.models import model_dims, init_params
    from repro.runtime import ServeFaultInjector
    from repro.serve import Engine, EngineConfig, Request

    cfg = dataclasses.replace(reduced(ARCHS["granite-8b"]), num_layers=2)
    params = init_params(jax.random.PRNGKey(2), cfg, model_dims(cfg, tp=1))
    bs = cfg.kv_block_size
    ptr = lambda a: [s.data.unsafe_buffer_pointer()
                     for s in a.addressable_shards]
    for spec in (None, "ngram"):
        inj = ServeFaultInjector(preempt_at=[(3, "pre", "auto"),
                                             (6, "post", "auto")])
        eng = Engine(cfg, params, EngineConfig(
            max_batch=4, max_seq_len=8 * bs, mesh_shape=(1, 2),
            prefill_budget=bs, auto_release=True, prefix_cache="auto",
            spec_decode=spec, fault_injector=inj))
        rng = np.random.RandomState(5)
        shared = rng.randint(0, cfg.vocab_size, 2 * bs)
        for i in range(5):
            tail = rng.randint(0, cfg.vocab_size, bs)
            eng.submit(Request(seq_id=i, max_new_tokens=6,
                               prompt=np.concatenate([shared, tail])))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            while eng.has_unfinished():
                old = eng.dstate["k_pool"]
                before = ptr(old)
                eng.poll()
                assert old.is_deleted()
                assert ptr(eng.dstate["k_pool"]) == before
        assert not [w for w in caught if "donated" in str(w.message)]
        s = eng.stats()
        assert s["pool_writes"]["dispatches"] > 0
        assert s["overload"]["request_resumes"] >= 1
        print(spec, "donated in place", flush=True)
""")


def test_sharded_steps_donate_in_place():
    """On a (1, 2) mesh the ``*_sharded`` step programs and the pool
    writes (migrations, resume) consume the pools and write them into
    the same per-shard buffers, with no donation refused."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.count("donated in place") == 2, out.stdout
