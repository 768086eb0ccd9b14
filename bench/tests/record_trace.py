#!/usr/bin/env python3
"""Records the small trace that ``test_bench_trace.py`` reduces.

    python bench/tests/record_trace.py <out_dir>

On a TPU: three rounds of two jitted programs named like the engine's
(``serve_step``, ``prefill_step``) under the harness's host spans, with
a 5 ms sleep between rounds, all inside one ``bench.trace`` span.  The
``.xplane.pb`` it writes under ``<out_dir>`` is committed as
``data/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def serve_step(a, b):
    return jnp.tanh(a @ b) @ b


@jax.jit
def prefill_step(a):
    return (a * 2.0).sum(axis=0)


def main(out_dir: str) -> None:
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(serve_step(a, b))
    jax.block_until_ready(prefill_step(a))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.trace"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"):
                x = serve_step(a, b)
                jax.device_get(prefill_step(x))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "small_trace")
