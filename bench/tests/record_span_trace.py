#!/usr/bin/env python3
"""Record the small TPU trace with program spans that
``test_trace_spans.py`` reads.

    PYTHONPATH=src python3 bench/tests/record_span_trace.py <out_dir>

Runs on one chip: three rounds, each a ``bench.step`` holding a
``runtime.step`` program span. In it a small jitted program is dispatched
(``session.dispatch``) and waited for (``session.decode.wait``), then
10 ms of host-only work (``session.accounting``), then a matmul program
is dispatched and waited for likewise; each round is followed by a 20 ms
``bench.idle_wait``. The small program parts the idle wait from the host
work, so each leaves a gap of its own. All inside one
``bench.trace_window``; the profiler's ``.xplane.pb`` is written under
``out_dir``.
"""
import sys
import time

import jax
import jax.numpy as jnp

from repro.runtime.telemetry import span

HOST_S = 0.01


def main(out: str) -> None:
    big = jax.jit(lambda a: jnp.tanh(a @ a).sum(0))
    small = jax.jit(lambda a: a * 2 + 1)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    big(x).block_until_ready(), small(x).block_until_ready()

    def device(fn):
        with span(None, "session.dispatch"):
            y = fn(x)
        with span(None, "session.decode.wait"):
            y.block_until_ready()

    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                with span(None, "runtime.step"):
                    device(small)
                    with span(None, "session.accounting"):
                        time.sleep(HOST_S)
                    device(big)
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
