#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace.py`` reduces: on a
TPU, a few top-k reads of a 2^14-row store through the program's kernel
and a few matrix products, inside the benchmark's spans, with host gaps
between them. Writes ``<out>/*.xplane.pb``.

    python3 bench/tests/record_trace.py <out-dir>
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro.core import memory as mem

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    C, E = 1 << 14, 384
    state = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E))
    state = mem.add_batch(
        state, jax.random.normal(jax.random.PRNGKey(0), (C, E)),
        jnp.zeros((C, 8), jnp.int32), jnp.zeros((C,), bool),
        jnp.zeros((C,), bool), jnp.arange(C, dtype=jnp.int32))
    qs = jax.random.normal(jax.random.PRNGKey(1), (4, E))
    mm = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    for _ in range(2):                      # compile outside the trace
        mem.query_topk_batch(state, qs, 1).device_get()
        mm(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tier.weak"):
                mm(a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.embed"):
                time.sleep(0.002)
            mem.query_topk_batch(state, qs, 1).device_get()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
