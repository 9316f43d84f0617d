"""Host cost of the serve path's span helper and sync count.

Times ``repro.serving.metrics.span`` (with and without metadata), a bare
``jax.profiler.TraceAnnotation`` and ``count_syncs`` with no profiler
session open, then ``span`` inside one, and prints the best and median
nanoseconds per call over several repeats (the loop's own cost, printed
first, is included in each).

Usage: PYTHONPATH=src python scripts/span_cost.py
"""
import os
import tempfile
import time

import jax

from repro.serving import metrics as M


def per_call_ns(fn, n, reps):
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        fn(n)
        out.append((time.perf_counter() - t) / n * 1e9)
    out.sort()
    return out[0], out[len(out) // 2]


def loop(n):
    for _ in range(n):
        pass


def span(n):
    for _ in range(n):
        with M.span("rar.decide"):
            pass


def span_tags(n):
    for i in range(n):
        with M.span("rar.batch", batch=i, wait_us=i):
            pass


def annotation(n):
    for _ in range(n):
        with jax.profiler.TraceAnnotation("rar.decide"):
            pass


def count_syncs(n, registry=M.MetricsRegistry()):
    for _ in range(n):
        M.count_syncs(registry, "engine")


def main():
    print(f"host cores {os.cpu_count()}")
    for name, fn in [("loop", loop), ("span_off", span),
                     ("span_off_tags", span_tags),
                     ("annotation_off", annotation),
                     ("count_syncs", count_syncs)]:
        best, med = per_call_ns(fn, 200_000, 7)
        print(f"{name} best {best:.1f} ns median {med:.1f} ns")
    jax.profiler.start_trace(tempfile.mkdtemp())
    try:
        for name, fn in [("span_on", span), ("span_on_tags", span_tags)]:
            best, med = per_call_ns(fn, 20_000, 3)
            print(f"{name} best {best:.1f} ns median {med:.1f} ns")
    finally:
        jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
