#!/usr/bin/env python3
"""Records the scoped trace that ``test_bench_scopes.py`` reduces.

    python bench/tests/record_scoped_trace.py <out_dir>

On a TPU: three engine steps shaped like the serving engine's, inside one
``bench.trace`` span.  Each step, under ``bench.poll`` / ``engine.step``,
sleeps 4 ms in ``engine.admit`` (host work, device idle), uploads a table
delta with ``_scatter_delta`` in ``engine.sync_translation``, dispatches
``serve_step`` in ``engine.dispatch`` -- a layer loop whose ops carry the
decode step's named scopes, ``kv_pool`` inside the loop -- and fetches
its token in ``engine.fetch``, sleeping 3 ms there after the fetch
(device idle inside the fetch).  5 ms of ``bench.wait`` follow each step.
The first step's admit also dispatches, in ``engine.prefill_dispatch``,
a ``prefill_step`` and a ``prefix_prefill_step`` whose ops carry the
prefill programs' scopes; the second step's dispatch first compiles a
program no warm-up ran (``late``).  The ``.xplane.pb`` it writes under
``<out_dir>`` is committed as ``data/scoped.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp

LAYERS = 4
SPAN = jax.profiler.TraceAnnotation


@jax.jit
def serve_step(pool, x, w):
    with jax.named_scope("translate"):
        rows = (jnp.arange(x.shape[0]) * 7) % x.shape[0]
        x = x[rows]

    def body(carry, i):
        pool, x = carry
        with jax.named_scope("kv_pool"):
            layer = jax.lax.dynamic_index_in_dim(pool, i, 0, keepdims=False)
        with jax.named_scope("attn"):
            x = jnp.tanh(x @ layer)
            layer = layer.at[0].set(x[0])
        with jax.named_scope("kv_pool"):
            pool = jax.lax.dynamic_update_index_in_dim(pool, layer, i, 0)
        with jax.named_scope("ffn"):
            x = jnp.tanh(x @ w)
        return (pool, x), None

    (pool, x), _ = jax.lax.scan(body, (pool, x), jnp.arange(LAYERS))
    with jax.named_scope("lm_head"):
        logits = x @ w
    with jax.named_scope("sample"):
        token = jnp.argmax(logits, axis=-1)
    return pool, token


@jax.jit
def _scatter_delta(table, idx, vals):
    return table.at[idx].set(vals)


def _layer(pool, x, w, i, prefix):
    with jax.named_scope("attn"):
        kv = x @ w
        if prefix is not None:
            with jax.named_scope("prefix_read"):
                kv = kv + jnp.tanh(prefix @ w)
        x = jnp.tanh(x @ kv.T @ w)
    with jax.named_scope("kv_install"):
        pool = jax.lax.dynamic_update_index_in_dim(pool, kv, i, 0)
    with jax.named_scope("ffn"):
        x = jnp.tanh(x @ w)
    return pool, x


def _prefill(pool, x, w, prefix):
    def body(carry, i):
        return _layer(*carry, w, i, prefix), None

    (pool, x), _ = jax.lax.scan(body, (pool, x), jnp.arange(LAYERS))
    with jax.named_scope("lm_head"):
        logits = x[-1:] @ w
    with jax.named_scope("sample"):
        token = jnp.argmax(logits, axis=-1)
    return pool, token


@jax.jit
def prefill_step(pool, x, w):
    return _prefill(pool, x, w, None)


@jax.jit
def prefix_prefill_step(pool, x, w, prefix):
    return _prefill(pool, x, w, prefix)


@jax.jit
def late(x):
    return jnp.tanh(x) * 2


def main(out_dir: str) -> None:
    pool = jnp.ones((LAYERS, 1024, 1024), jnp.bfloat16)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    table = jnp.zeros(4096, jnp.int32)
    idx = jnp.arange(16) * 3
    vals = jnp.arange(16, dtype=jnp.int32)
    jax.block_until_ready(serve_step(pool, x, w))
    jax.block_until_ready(_scatter_delta(table, idx, vals))
    jax.block_until_ready(prefill_step(pool, x, w))
    jax.block_until_ready(prefix_prefill_step(pool, x, w, x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with SPAN("bench.trace"):
        for step in range(3):
            with SPAN("bench.poll"), SPAN("engine.step"):
                with SPAN("engine.admit"):
                    time.sleep(0.004)
                    if step == 0:
                        with SPAN("engine.prefill_dispatch"):
                            pool, _ = prefill_step(pool, x, w)
                            pool, _ = prefix_prefill_step(pool, x, w, x)
                with SPAN("engine.sync_translation"):
                    table = _scatter_delta(table, idx, vals)
                with SPAN("engine.dispatch"):
                    if step == 1:
                        x = late(x)
                    pool, token = serve_step(pool, x, w)
                with SPAN("engine.fetch"):
                    jax.device_get(token)
                    time.sleep(0.003)
                with SPAN("engine.commit"):
                    pass
            with SPAN("bench.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "scoped_trace")
