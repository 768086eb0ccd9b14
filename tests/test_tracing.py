"""Tracing inside the program: named scopes in the step programs, engine
phase spans in the profiler's trace, and per-request queue wait.

* scopes are trace-time metadata: the compiled programs' instructions are
  the same with and without them (metadata stripped), and the lowered
  decode step names every scope the benchmark reads;
* the profiler changes nothing the engine computes: greedy streams are
  bit-identical with a trace running, and the trace holds the engine's
  phase spans;
* ``queue_wait_s`` / ``admitted_at`` count submit -> first chunk admitted
  and survive a preempt/resume and a snapshot/restore;
* a persistent compile cache is keyed on the programs' metadata, so a
  cached build from before the scopes never stands in for them.
"""
import glob
import inspect
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util
from jax._src.lib import xla_client

from repro.configs import ARCHS, reduced
from repro.models import model_dims, init_params
from repro.runtime import ServeFaultInjector
from repro.serve import Engine, EngineConfig, Request
from repro.serve.decode import DecodeSpec, init_decode_state, make_serve_step
from repro.serve.prefill import make_prefill_step, make_prefix_prefill_step
from repro.serve.spec_decode import make_spec_decode_step

DECODE_SCOPES = ("translate", "kv_pool", "attn", "ffn", "lm_head", "sample")
PREFILL_SCOPES = ("attn", "ffn", "lm_head", "sample", "kv_install",
                  "prefix_read")
ALL_SCOPES = set(DECODE_SCOPES + PREFILL_SCOPES)
PHASES = ("engine.step", "engine.admit", "engine.prefill_dispatch",
          "engine.apply_copies", "engine.sync_translation",
          "engine.dispatch", "engine.fetch", "engine.commit",
          "engine.promotions")


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["granite-8b"])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    return cfg, dims, params


def _spec(cfg):
    return DecodeSpec(block_size=cfg.kv_block_size, max_blocks_per_seq=4,
                      slots_per_group=16, n_sets=2, assoc=4)


def _programs(cfg, dims, params, kind):
    """(function, args) of one step program at a tiny shape."""
    spec = _spec(cfg)
    B, bs = 2, cfg.kv_block_size
    dstate = init_decode_state(cfg, dims, spec, B, 1)
    i32 = lambda *a: jnp.asarray(np.asarray(a, np.int32))
    if kind == "serve":
        fn = make_serve_step(cfg, dims, spec, dtype=jnp.float32)
        return fn, (params, dstate, i32(1, 2), jnp.ones(B, bool))
    if kind == "spec":
        dstate = dict(dstate, hist=-jnp.ones((B, 4 * bs), jnp.int32))
        fn = make_spec_decode_step(cfg, dims, spec, 2, dtype=jnp.float32)
        return fn, (params, dstate, i32(1, 2), jnp.ones(B, bool))
    toks = {"tokens": jnp.ones((B, bs), jnp.int32)}
    if kind == "prefill":
        fn = make_prefill_step(cfg, dims, spec)
        return fn, (params, dstate, toks, i32([0], [1]), i32(0, 1),
                    i32(bs, bs), i32(bs - 1, bs - 1))
    fn = make_prefix_prefill_step(cfg, dims, spec)
    return fn, (params, dstate, toks, i32([2], [3]), i32([0, -1], [1, -1]),
                i32(0, 1), i32(2 * bs, 2 * bs), i32(bs, bs),
                i32(bs - 1, bs - 1))


def _no_scopes(monkeypatch):
    """Make the program's own named scopes push nothing on the name
    stack (JAX's own entries, such as ``while/body``, stay)."""
    cm = source_info_util.ExtendNameStackContextManager
    orig = cm.__enter__

    def enter(self):
        if self.name in ALL_SCOPES:
            self.prev = source_info_util._source_info_context.context
            return self.prev.name_stack
        return orig(self)

    monkeypatch.setattr(cm, "__enter__", enter)


def _instructions(compiled) -> str:
    """The optimized HLO with its metadata (op names, source lines) left
    out and instructions named by position: the names XLA derives from
    the op names are labels, not instructions."""
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = False
    opts.canonicalize_instruction_names = True
    return "\n".join(m.to_string(opts) for m in
                     compiled.runtime_executable().hlo_modules())


@pytest.mark.parametrize("kind", ["serve", "spec", "prefill", "prefix"])
def test_scopes_leave_the_compiled_program_unchanged(setup, monkeypatch,
                                                     kind):
    cfg, dims, params = setup
    fn, args = _programs(cfg, dims, params, kind)
    scoped = jax.jit(fn).lower(*args).compile()
    _no_scopes(monkeypatch)
    fn, args = _programs(cfg, dims, params, kind)
    plain = jax.jit(fn).lower(*args).compile()
    assert re.search(r"/(attn|kv_install)/", scoped.as_text())
    assert not re.search(r"/(attn|kv_install)/", plain.as_text())
    assert _instructions(scoped) == _instructions(plain)


def _op_names(fn, args):
    """(lowered text, the op names of the optimized HLO's metadata) --
    the names a device trace reports as each op's ``tf_op``."""
    lowered = jax.jit(fn).lower(*args)
    return (lowered.as_text(debug_info=True),
            set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text())))


def test_lowered_serve_step_names_every_scope(setup):
    cfg, dims, params = setup
    text, names = _op_names(*_programs(cfg, dims, params, "serve"))
    for scope in DECODE_SCOPES:
        assert re.search(rf'loc\("[^"]*\b{scope}[/"]', text), scope
        assert any(n.startswith("jit(serve_step)/") and f"/{scope}/" in n
                   for n in names), scope
    # the in-place pool write is its own scope, inside the layer loop
    assert any(re.match(r"jit\(serve_step\)/while/body/.*kv_pool/scatter",
                        n) for n in names)


def test_prefix_step_names_its_scopes(setup):
    cfg, dims, params = setup
    text, names = _op_names(*_programs(cfg, dims, params, "prefix"))
    for scope in PREFILL_SCOPES:
        assert re.search(rf'loc\("[^"]*\b{scope}[/"]', text), scope
        assert any(f"/{scope}/" in n for n in names), scope
    # the prefix read nests inside attention
    assert any("/attn/prefix_read/" in n for n in names)


def _prompts(cfg, n, seed=0):
    """``n`` prompts of 1-3 blocks (at least 2 from the second on)."""
    rng = np.random.RandomState(seed)
    bs = cfg.kv_block_size
    return [rng.randint(0, cfg.vocab_size, (1 + (i + 1) % 3) * bs)
            for i in range(n)]


def _serve(cfg, params, prompts, **kw):
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=8 * bs, auto_release=True, **kw))
    for i, p in enumerate(prompts):
        eng.submit(Request(seq_id=i, prompt=p, max_new_tokens=5))
    streams = {i: [] for i in range(len(prompts))}
    while eng.has_unfinished():
        for out in eng.poll():
            streams[out.seq_id].extend(out.new_token_ids)
    return eng, streams


def test_profiler_leaves_greedy_streams_identical(setup, tmp_path):
    cfg, _, params = setup
    prompts = _prompts(cfg, 4)
    _, plain = _serve(cfg, params, prompts)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, traced = _serve(cfg, params, prompts)
    finally:
        jax.profiler.stop_trace()
    assert traced == plain
    assert all(len(s) == 5 for s in plain.values())
    # the engine's phases are spans in the profiler's own trace
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events}
    assert set(PHASES) <= names, set(PHASES) - names


def test_queue_wait_counts_submit_to_first_chunk(setup):
    cfg, _, params = setup
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=8 * bs, auto_release=True,
        prefill_budget=2 * bs))
    t_submit = time.perf_counter()
    for i, p in enumerate(_prompts(cfg, 3)):
        eng.submit(Request(seq_id=i, prompt=p[:2 * bs], max_new_tokens=3))
    t_after = time.perf_counter()
    rows = eng.stats()["per_request"]
    assert all(r["queue_wait_s"] is None and r["admitted_at"] is None
               for r in rows.values())
    eng.step()          # the budget admits one 2-block prompt per step
    rows = eng.stats()["per_request"]
    assert rows[0]["admitted_at"] is not None
    assert rows[1]["admitted_at"] is None
    while eng.has_unfinished():
        eng.step()
    rows = eng.stats()["per_request"]
    waits = [rows[i]["queue_wait_s"] for i in range(3)]
    adm = [rows[i]["admitted_at"] for i in range(3)]
    assert all(w >= 0 for w in waits)
    assert adm == sorted(adm) and waits == sorted(waits)
    for w, a in zip(waits, adm):
        # admitted_at - queue_wait_s is the submit time
        assert t_submit <= a - w <= t_after
    assert adm[0] <= adm[1] <= adm[2] <= time.perf_counter()


def test_queue_wait_survives_preempt_and_resume(setup):
    cfg, _, params = setup
    bs = cfg.kv_block_size
    inj = ServeFaultInjector(preempt_at=[(3, "post", 0)])
    eng = Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=8 * bs, pool_headroom=2.0,
        auto_release=True, fault_injector=inj))
    for i, p in enumerate(_prompts(cfg, 2)):
        eng.submit(Request(seq_id=i, prompt=p, max_new_tokens=8))
    eng.step()
    first = {sid: (r["queue_wait_s"], r["admitted_at"])
             for sid, r in eng.stats()["per_request"].items()}
    assert all(a is not None for _, a in first.values())
    while eng.has_unfinished():
        eng.step()
    rows = eng.stats()["per_request"]
    assert rows[0]["preempts"] == 1 and rows[0]["resumes"] == 1
    for sid, (w, a) in first.items():
        assert (rows[sid]["queue_wait_s"], rows[sid]["admitted_at"]) == (w, a)


def test_queue_wait_clock_moves_with_a_restore(setup):
    cfg, _, params = setup
    bs = cfg.kv_block_size
    conf = EngineConfig(max_batch=4, max_seq_len=8 * bs, auto_release=True,
                        prefill_budget=2 * bs)
    t0 = time.perf_counter()
    eng = Engine(cfg, params, conf)
    for i, p in enumerate(_prompts(cfg, 2)):
        eng.submit(Request(seq_id=i, prompt=p[:2 * bs], max_new_tokens=3))
    eng.step()                       # request 1 is still queued
    snap = eng.snapshot()
    time.sleep(0.05)                 # the pause does not count as waiting
    fresh = Engine(cfg, params, conf)
    fresh.restore(snap)
    while fresh.has_unfinished():
        fresh.step()
    elapsed = time.perf_counter() - t0
    rows = fresh.stats()["per_request"]
    assert rows[0]["queue_wait_s"] <= rows[1]["queue_wait_s"]
    assert rows[1]["queue_wait_s"] <= elapsed - 0.05
    assert rows[1]["admitted_at"] <= time.perf_counter()


KEY_FLAG = "jax_compilation_cache_include_metadata_in_key"
LOCATION_FLAGS = ("jax_traceback_in_locations_limit",
                  "jax_hlo_source_file_canonicalization_regex")
CACHE_FLAGS = ("jax_compilation_cache_dir", KEY_FLAG) + LOCATION_FLAGS


@pytest.fixture
def cache_config():
    """JAX's cache directory, key and location flags, restored after."""
    was = {f: getattr(jax.config, f) for f in CACHE_FLAGS}
    jax.config.update(KEY_FLAG, False)
    yield
    for f, v in was.items():
        jax.config.update(f, v)


def test_make_engine_keys_only_a_cache_on_metadata(setup, cache_config,
                                                    tmp_path, monkeypatch):
    """Without a persistent cache ``make_engine`` leaves JAX's cache key
    alone; with one placed by its caller the key holds the programs'
    metadata, so a cached build without the named scopes never loads."""
    from repro.launch import serve as launch_serve
    cfg, _, params = setup
    monkeypatch.setattr(launch_serve, "Engine", lambda *a, **k: None)
    kw = dict(max_batch=2, max_seq_len=4 * cfg.kv_block_size)
    jax.config.update("jax_compilation_cache_dir", None)
    launch_serve.make_engine(cfg, params, **kw)
    assert not getattr(jax.config, KEY_FLAG)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    launch_serve.make_engine(cfg, params, **kw)
    assert getattr(jax.config, KEY_FLAG)


def test_enable_compile_cache_keys_on_checkout_relative_op_lines(
        cache_config, tmp_path, monkeypatch):
    """The key holds each op's own line, named from the checkout's root:
    neither the call path that first traced a program nor where the
    checkout lies changes it."""
    from repro.launch import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert getattr(jax.config, KEY_FLAG)
    assert jax.config.jax_traceback_in_locations_limit == 1
    rx = jax.config.jax_hlo_source_file_canonicalization_regex
    here = os.path.abspath(__file__)
    assert re.sub(rx, "", here) == os.path.join("tests", "test_tracing.py")

    def scaled(x):
        with jax.named_scope("ffn"):
            return x * 2

    f = jax.jit(scaled)
    call_line = inspect.currentframe().f_lineno + 1
    text = f.lower(jnp.ones(3)).as_text(debug_info=True)
    assert '"tests/test_tracing.py"' in text and here not in text
    # one frame: the op's line, not this test's call of ``f``
    assert f'"tests/test_tracing.py":{call_line}:' not in text
    # the compiled program still names the scope of each op
    assert 'op_name="jit(scaled)/ffn/mul"' in f.lower(
        jnp.ones(3)).compile().as_text()


def test_cache_key_flags_keep_the_decode_step_op_names(setup,
                                                       cache_config):
    """The profile reads each op's scope from its compiled ``op_name``:
    keying the cache on metadata leaves every op name as it was."""
    from repro.launch.compile_cache import key_on_metadata
    cfg, dims, params = setup

    def op_names():
        fn, args = _programs(cfg, dims, params, "serve")
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        return sorted(re.findall(r'op_name="([^"]*)"', hlo))

    before = op_names()
    key_on_metadata()
    after = op_names()
    assert after == before
    assert any("/kv_pool/" in n for n in after)
