"""``enable_compile_cache``: the directory comes from outside the program.

Each case runs in a fresh interpreter: turning the persistent cache on is
process-wide, and the rest of the suite runs without one.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.compile_cache import DEFAULT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_dir):
    script = textwrap.dedent("""
        import os
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        where = enable_compile_cache()
        print("WHERE", where)
        print("CONFIG", jax.config.jax_compilation_cache_dir)
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    res = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in res.stdout.splitlines()
                 if line.startswith(("WHERE", "CONFIG")))
    return lines["WHERE"], lines["CONFIG"]


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_var_set", "env_var_unset"])
def test_cache_directory(tmp_path, from_env):
    env_dir = tmp_path / "cache" if from_env else None
    where, config = _run(env_dir)
    if from_env:
        # JAX reads the variable itself; the program sets no other place
        assert where == config == str(env_dir)
        assert any(env_dir.iterdir()), "nothing was cached there"
    else:
        assert where == config == DEFAULT_DIR
        assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
