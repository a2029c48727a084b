#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs on one chip: two jitted programs, a few calls each inside harness
spans (``bench.step``, ``bench.idle_wait``) within one
``bench.trace_window``, and writes the profiler's ``.xplane.pb`` under
``out_dir``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    big = jax.jit(lambda a: jnp.tanh(a @ a).sum(0))
    small = jax.jit(lambda a: a * 2 + 1)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    big(x).block_until_ready(), small(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                big(x).block_until_ready()
                small(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
