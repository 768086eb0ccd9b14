"""The scope and engine-phase reduction (``bench/scopes.py``).

``data/scoped.xplane.pb`` was recorded on a TPU v5e by
``record_scoped_trace.py``: three engine-shaped steps, each sleeping 4 ms
in ``engine.admit`` and 3 ms in ``engine.fetch`` after the fetch (whose
own wait for the token's transfer adds about 1.6 ms with no device op),
with a scoped ``serve_step`` (a four-layer loop, ``kv_pool`` inside it)
and a ``_scatter_delta`` upload, 5 ms of ``bench.wait`` after each; the
first step's admit dispatches a scoped ``prefill_step`` and
``prefix_prefill_step``, and the second step compiles a program.
``data/small.xplane.pb`` (``record_trace.py``) has an unscoped
``serve_step`` and no engine spans."""
import os
import shutil
import statistics
import types

import pytest

from bench import scopes, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
SMALL = os.path.join(DATA, "small.xplane.pb")
READERS = ("translation.ms_per_step", "decode.kv_pool_ms", "engine.host_ms",
           "device.idle_fetch_share", "sched.queue_wait_ms")


@pytest.fixture(scope="module")
def red():
    return scopes.reduce(SCOPED)


@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_reader_agrees_with_profile_data(path):
    ours, theirs = scopes.read_planes(path), trace.read_planes(path)
    assert ours["host"] == theirs["host"] and ours["host"]
    assert set(ours["devices"]) == set(theirs["devices"])
    for name, dev in theirs["devices"].items():
        assert ours["devices"][name]["modules"] == dev["modules"]
        assert [op[:3] for op in ours["devices"][name]["ops"]] == dev["ops"]


def test_tf_op_agrees_with_tensorflow():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(SCOPED, "rb") as fh:
        space.ParseFromString(fh.read())
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    stat = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            md = plane.event_metadata[e.metadata_id]
            want[md.name] = next((s.str_value for s in md.stats
                                  if stat[s.metadata_id] == "tf_op"), "")
    ours = {op[0]: op[3] for op in
            scopes.read_planes(SCOPED)["devices"]["/device:TPU:0"]["ops"]}
    assert ours == want
    assert any("/kv_pool/" in v for v in ours.values())


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(serve_step)/while/body/closed_call/kv_pool/dynamic_slice:",
     "kv_pool"),
    ("jit(serve_step)/translate/vmap()/gather:", "translate"),
    ("jit(prefix_prefill_step)/while/body/attn/prefix_read/dot_general:",
     "prefix_read"),
    ("jit(serve_step)/while/body/attn/reshape;attn/broadcast_in_dim;attn",
     "attn"),
    ("jit(serve_step)/dot_general:", "unscoped"),
    ("jit(serve_step)/while", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope_of_a_name_stack(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_self_time_leaves_out_nested_ops():
    ops = [("while", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 40.0, 70.0),
           ("b.inner", 45.0, 50.0), ("after", 100.0, 120.0)]
    assert scopes.self_times(ops) == [50.0, 20.0, 25.0, 5.0, 20.0]
    # self times add up to the busy time
    assert sum(scopes.self_times(ops)) == trace.total(
        trace.union([op[1:] for op in ops]))


def test_scoped_decode_program_is_split_by_scope(red):
    assert red["scoped"] and red["decode_n"] == 3
    # the argmax fused into the head's matmul: a fusion carries the name
    # of its root, so ``sample`` reads nothing here
    for scope in ("translate", "kv_pool", "attn", "ffn", "lm_head"):
        assert red["split_s"].get(scope, 0.0) > 0, scope
    assert "sample" not in red["split_s"]
    # the split is the decode program's time, each op counted once
    dec, n = trace.module_time(trace.reduce(SCOPED), r"^jit_serve_step")
    assert n == 3
    assert 0.8 * dec <= sum(red["split_s"].values()) <= dec * (1 + 1e-6)
    assert red["scatter_s"] > 0


def test_unscoped_program_reads_none():
    red = scopes.reduce(SMALL)
    assert not red["scoped"] and red["decode_n"] == 3
    assert set(red["split_s"]) == {scopes.UNSCOPED}
    assert red["steps"] == 0 and red["idle_fetch_s"] == 0


def test_idle_inside_the_fetch_is_attributed_to_it(red):
    # three 3 ms sleeps inside engine.fetch after the device finished, and
    # the wait for the token's transfer to the host, when no op runs
    host = scopes.read_planes(SCOPED)["host"]
    fetch = sum(e - s for n, s, e in host if n == "engine.fetch") * 1e-9
    assert 0.009 <= red["idle_fetch_s"] <= fetch
    assert red["idle_by_phase_s"]["engine.fetch"] == pytest.approx(
        red["idle_fetch_s"])
    assert red["idle_by_phase_s"]["engine.admit"] >= 0.012
    assert red["idle_by_phase_s"]["bench.wait"] >= 0.015
    assert red["idle_engine_share"] > 0.9
    # host time of a step outside its fetch holds the 4 ms admit sleep
    assert red["steps"] == 3
    assert 0.004 <= statistics.median(red["host_step_s"]) < 0.004 + 0.004


def test_host_time_of_a_step_leaves_out_its_compile(red):
    host = scopes.read_planes(SCOPED)["host"]
    w0 = next(s for n, s, e in host if n == trace.WINDOW_SPAN)
    steps = sorted((s, e) for n, s, e in host if n == scopes.STEP)
    comp = [(w0 + a * 1e9, w0 + b * 1e9) for a, b in red["compiles"]]
    # the second step's compile, and only it
    assert comp and all(steps[1][0] <= a and b <= steps[1][1]
                        for a, b in comp)
    assert red["compile_s"] == pytest.approx(
        sum(b - a for a, b in red["compiles"]))
    whole = (steps[1][1] - steps[1][0]) * 1e-9
    assert red["host_step_s"][1] <= whole - red["compile_s"]
    # what is left is the 4 ms sleep and the first call's tracing around
    # the compile events, not the 0.16 s compile
    assert red["host_step_s"][1] < 0.1 * whole


def test_prefill_programs_are_split_by_scope(red):
    assert red["prefill_n"] == {"jit_prefill_step": 1,
                                "jit_prefix_prefill_step": 1}
    whole = trace.reduce(SCOPED)
    # (the toy's ``kv_install`` write is fused into the loop's matmul
    # fusion, which carries the name of its root; the engine's reads > 0
    # on the chip)
    for prog, scopes_in in (
            ("jit_prefill_step", ("attn", "ffn")),
            ("jit_prefix_prefill_step", ("attn", "ffn", "prefix_read"))):
        split = red["prefill_split_s"][prog]
        for scope in scopes_in:
            assert split.get(scope, 0.0) > 0, (prog, scope)
        dev, n = trace.module_time(whole, "^" + prog + "$")
        assert n == 1
        assert 0.8 * dev <= sum(split.values()) <= dev * (1 + 1e-6)
    assert "prefix_read" not in red["prefill_split_s"]["jit_prefill_step"]
    line = scopes.summary(red).splitlines()[2]
    assert "jit_prefix_prefill_step" in line and "prefix_read" in line


def test_clock_offset_pairs_the_decode_program_with_its_own_dispatch():
    ms = 1e6
    host = [("PjitFunction(serve_step)", t * ms, (t + 1) * ms)
            for t in (0, 40, 80, 120)]
    # a delta upload whose first call compiled for 240 ms: its device run
    # lies 40 ms before the next call's dispatch
    host += [("PjitFunction(_scatter_delta)", 100 * ms, 340 * ms),
             ("PjitFunction(_scatter_delta)", 380 * ms, 381 * ms)]
    dev = {"modules": [("jit_serve_step(1)", (t + 0.7) * ms, (t + 30) * ms)
                       for t in (0, 40, 80, 120)]
           + [("jit__scatter_delta(2)", 340.5 * ms, 341 * ms)]}
    assert trace._clock_offset(dev, host) == pytest.approx(-39.5 * ms)
    assert scopes.clock_offset(dev, host) == 0.0
    # a device clock behind the host's shows as a negative offset
    early = {"modules": [(n, s - 2 * ms, e - 2 * ms)
                         for n, s, e in dev["modules"]]}
    assert scopes.clock_offset(early, host) == pytest.approx(-1.3 * ms)


def test_idle_time_goes_to_the_innermost_engine_phase():
    host = [("bench.trace", 0.0, 100.0), ("bench.poll", 0.0, 60.0),
            ("engine.step", 5.0, 55.0), ("engine.admit", 10.0, 20.0),
            ("engine.prefill_dispatch", 12.0, 15.0),
            ("engine.fetch", 30.0, 50.0), ("np.asarray", 31.0, 49.0),
            ("bench.wait", 70.0, 90.0)]
    segs = scopes.phase_segments(host)
    assert segs[0] == (0.0, 5.0, "bench.poll")
    assert (12.0, 15.0, "engine.prefill_dispatch") in segs
    assert (15.0, 20.0, "engine.admit") in segs
    gaps = [(0.0, 12.0), (40.0, 80.0), (95.0, 100.0)]
    by = {k: round(v * 1e9, 6) for k, v in
          scopes.idle_by_phase(gaps, segs).items()}
    assert by == {"bench.poll": 10.0, "engine.step": 10.0,
                  "engine.admit": 2.0, "engine.fetch": 10.0,
                  "host:none": 15.0, "bench.wait": 10.0}
    assert scopes.label(host, 40.0) == "bench.poll>engine.fetch>np.asarray"
    assert scopes.label(host, 80.0) == "bench.wait"
    assert scopes.label(host, 95.0) == "host:none"


def test_idle_gaps_are_labelled_with_the_engine_phase(red):
    labels = [lab for lab, _ in red["idle_gaps"]]
    assert any(lab.startswith("bench.poll>engine.admit") for lab in labels)
    # fetch, commit, wait and the next admit make one gap, labelled at
    # its middle; the phase split above gives each its share
    assert any(lab == "bench.wait" for lab in labels)
    host = scopes.read_planes(SCOPED)["host"]
    s, e = next((s, e) for n, s, e in host if n == "engine.fetch")
    assert scopes.label(host, e - 1e6).startswith("bench.poll>engine.fetch")
    lines = scopes.summary(red).splitlines()
    assert len(lines) == 4 and "engine host" in lines[0] \
        and "kv_pool" in lines[1] and "prefill" in lines[2] \
        and "engine.fetch" in lines[3]


def _run(tmp_path, xplane, cell="tiny.cell", stats=None, window=(0.0, 1.0),
         trace_window=None):
    """What a reader sees of a traced run whose trace is ``xplane``."""
    tdir = tmp_path / ".bench_out" / "trace" / cell / "plugins"
    tdir.mkdir(parents=True)
    shutil.copy(xplane, tdir / "host.xplane.pb")
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell,
                                   search=[str(tmp_path / "bench")]),
        trace={}, window=window, trace_window=trace_window,
        stats=stats or {"per_request": {}})


def _read(run, name):
    return spec.load_module(spec.search_dirs(spec.ROOT), "metrics",
                            name).read(run)


def test_readers_read_the_scoped_trace(tmp_path, red):
    run = _run(tmp_path, SCOPED)
    got = {name: _read(run, name) for name in READERS[:4]}
    n = red["decode_n"]
    assert got["decode.kv_pool_ms"] == pytest.approx(
        1e3 * red["split_s"]["kv_pool"] / n)
    assert got["translation.ms_per_step"] == pytest.approx(
        1e3 * (red["split_s"]["translate"] + red["scatter_s"]) / n)
    assert 4.0 <= got["engine.host_ms"] < 8.0
    assert 0 < got["device.idle_fetch_share"] < 100


def test_readers_of_an_unscoped_untimed_trace_read_none(tmp_path):
    run = _run(tmp_path, SMALL)
    assert {name: _read(run, name) for name in READERS} == dict.fromkeys(
        READERS)
    # and a run that was not traced reads nothing either
    run.trace = None
    assert _read(run, "decode.kv_pool_ms") is None


def test_queue_wait_reads_requests_admitted_in_the_window(tmp_path):
    rows = {0: {"queue_wait_s": 0.010, "admitted_at": 5.0},
            1: {"queue_wait_s": 0.030, "admitted_at": 6.0},
            2: {"queue_wait_s": 9.000, "admitted_at": 20.0},   # after
            3: {"queue_wait_s": None, "admitted_at": None},    # queued
            -1: {"queue_wait_s": 1.0, "admitted_at": 5.5}}     # warm-up
    run = _run(tmp_path, SMALL, stats={"per_request": rows},
               window=(4.0, 10.0))
    assert _read(run, "sched.queue_wait_ms") == pytest.approx(20.0)
    # the median: one request behind a compile does not move it
    rows[4] = {"queue_wait_s": 3.0, "admitted_at": 7.0}
    rows[5] = {"queue_wait_s": 0.015, "admitted_at": 8.0}
    assert _read(run, "sched.queue_wait_ms") == pytest.approx(22.5)
    # an engine that does not report the fields reads None
    run.stats = {"per_request": {0: {"cached_blocks": 1}}}
    assert _read(run, "sched.queue_wait_ms") is None


def test_queue_wait_split_by_compile_in_the_traced_part(tmp_path, red):
    # lowering and compiling ``late``, one stretch of the second step
    a, b, t0 = red["compiles"][0][0], red["compiles"][-1][1], 100.0
    rows = {0: {"queue_wait_s": 0.5, "admitted_at": t0 + b + 0.001},
            1: {"queue_wait_s": 0.002, "admitted_at": t0 + b + 0.004},
            2: {"queue_wait_s": 0.004, "admitted_at": t0 + a - 0.001},
            3: {"queue_wait_s": 0.9, "admitted_at": t0 - 0.5}}  # before
    run = _run(tmp_path, SCOPED, stats={"per_request": rows},
               window=(t0 - 1.0, t0 + 10.0),
               trace_window=(t0, t0 + red["window_s"]))
    reader = spec.load_module(spec.search_dirs(spec.ROOT), "metrics",
                              "sched.queue_wait_ms")
    hit, n_hit, clear, n_clear = reader.compile_split(run)
    assert (n_hit, n_clear) == (1, 2)
    assert hit == pytest.approx(500.0) and clear == pytest.approx(3.0)
    # an untraced run has no split
    assert reader.compile_split(_run(tmp_path / "u", SMALL)) is None
