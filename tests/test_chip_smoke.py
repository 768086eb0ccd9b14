"""``chip_smoke.py``'s phases on the CPU at ``reduced()`` size.

The script serves granite-8b at its published widths on a TPU; here the
same functions run with a reduced config passed in, so the script's path
(engine set-up, request mix, stream and invariant checks, the logits check
against the float32 reference) cannot rot between chip runs.  The script
run as a program must still refuse every platform but ``tpu``.
"""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.launch.serve import load_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
WL = dict(n_requests=8, prompt_len=32, shared_len=16, max_new=6,
          max_batch=4, max_seq_len=64)
# The script's absolute logits bound is read at granite-8b's widths on the
# chip.  At reduced() widths (vocab 256) the bf16 forward's own distance
# is larger and varies more between requests: 0.013099 and 0.015036 for
# the two checked requests of WL, on the CPU.  The bound here is set the
# same way, 1.15x the larger reading.
REDUCED_BF16_FORWARD = 0.015036


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it there
    spec.loader.exec_module(mod)
    mod.REL_L2_MAX = mod.LOGITS_RATIO_TOL * REDUCED_BF16_FORWARD
    return mod


@pytest.fixture(scope="module")
def model():
    return load_model("granite-8b", reduced=True, dtype="bfloat16")


def test_one_chip_phases_reduced(smoke, model):
    cfg, params = model
    hybrid, flex = smoke.smoke_one_chip(cfg, params, smoke.Workload(**WL))
    assert hybrid["mode"] == "hybrid" and flex["mode"] == "flexible_only"
    for s in (hybrid, flex):
        assert s["tokens"] == WL["n_requests"] * WL["max_new"]
        assert s["prefix_hits"] > 0
    assert hybrid["rsw_hits"] > 0 and flex["rsw_hits"] == 0
    # the unique request and the one whose prefix came from the cache
    assert sorted(hybrid["logits"]) == [WL["n_requests"] - 2,
                                        WL["n_requests"] - 1]
    for d in hybrid["logits"].values():
        assert (d["engine_rel_l2"]
                <= smoke.LOGITS_RATIO_TOL * d["bf16_forward_rel_l2"])
        assert d["engine_rel_l2"] <= smoke.REL_L2_MAX


def test_logits_check_fails_a_perturbed_engine(smoke, model):
    """The check is not vacuous: logits off by more than the tolerance
    allows (here: a uniform 10% rescale) fail it."""
    import numpy as np
    cfg, params = model
    reqs, streams, rows, _ = smoke.run_mode(cfg, params,
                                            smoke.Workload(**WL), "hybrid")
    target = reqs[-1]
    bad = [(k, np.asarray(r, np.float32) * 1.1)
           for k, r in rows[target.seq_id]]
    with pytest.raises(smoke.SmokeFailure, match="as far from"):
        smoke.check_logits(cfg, params, target, streams[target.seq_id], bad)


def test_logits_check_fails_a_shared_bf16_norm(smoke, monkeypatch):
    """A float32 -> bf16 slip in code the engine shares with ``forward``
    raises both distances, so their ratio cannot see it; the absolute
    bound does.  ``REL_L2_MAX`` is set here as it is for the chip (1.15x
    the clean bf16 forward's distance) at a width where the slip shows
    (d_model 512, 8 layers: reduced() widths are too narrow for it), and
    an RMS norm computed in bf16 must then fail the check."""
    import jax
    import jax.numpy as jnp
    import repro.models.layers as L
    from repro.configs import get_config, reduced
    from repro.models import init_params, model_dims

    cfg = dataclasses.replace(
        reduced(get_config("granite-8b")), d_model=512, num_heads=8,
        num_kv_heads=2, head_dim=64, d_ff=1024, num_layers=8)
    params = init_params(jax.random.PRNGKey(0), cfg, model_dims(cfg, tp=1),
                         dtype=jnp.bfloat16)

    def unique_request_logits():
        reqs, streams, rows, _ = smoke.run_mode(
            cfg, params, smoke.Workload(**WL), "hybrid")
        target = reqs[-1]
        return smoke.check_logits(cfg, params, target,
                                  streams[target.seq_id],
                                  rows[target.seq_id])

    monkeypatch.setattr(smoke, "REL_L2_MAX", float("inf"))
    clean = unique_request_logits()
    monkeypatch.setattr(smoke, "REL_L2_MAX", smoke.LOGITS_RATIO_TOL
                        * clean["bf16_forward_rel_l2"])
    assert clean["engine_rel_l2"] <= smoke.REL_L2_MAX

    def bf16_rms_norm(x, scale, eps=1e-5):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype)

    monkeypatch.setattr(L, "rms_norm", bf16_rms_norm)
    with pytest.raises(smoke.SmokeFailure,
                       match=r"[0-9] from the float32 reference"):
        unique_request_logits()


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_script_refuses_without_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_four_chip_phase_on_host_devices():
    """``--four-chips``' phase on four forced host devices: the mesh
    (1, 4) engine passes the logits check and, on the CPU, matches the
    single-device engine bit for bit (DESIGN.md §sharded-serving)."""
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.launch.serve import load_model
        cs.REL_L2_MAX = cs.LOGITS_RATIO_TOL * {REDUCED_BF16_FORWARD!r}
        cfg, params = load_model("granite-8b", reduced=True,
                                 dtype="bfloat16")
        err, same = cs.smoke_four_chips(cfg, params, cs.Workload(**{WL!r}))
        assert err == 0.0 and same, (err, same)
        print("ALL_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL_OK" in res.stdout, (
        res.stdout[-2000:], res.stderr[-4000:])
