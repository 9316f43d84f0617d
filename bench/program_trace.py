"""The program's own spans in a traced window: the ``rar.*`` spans that
the serve path opens (``repro.serving.metrics.span``), their durations
by name, each microbatch's ``rar.batch`` tags, and the device's idle
time named by the innermost ``rar.*`` span open on the host.

This reads the run's profile a second time, beside :mod:`bench.trace`,
whose reduction it leaves as it is. Idle time inside no ``rar.*`` span
keeps the label that :mod:`bench.trace` gives it (a ``bench.*`` probe,
``bench.serve`` or ``no_request``). A trace with no ``rar.*`` span, from
a program without them, reduces to no spans and no batches, and the
metrics that read them report nothing.

Each ``rar.batch`` span carries TraceMe metadata: ``batch`` (the
fabric's ticket id), ``wait_us`` (the microseconds the microbatch waited
in its replica's FIFO) and ``syncs`` (the blocking device-to-host
fetches made inside it).
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import statistics
import sys
import time

from bench import trace as TR

PREFIX = "rar."
BATCH = "rar.batch"
SUBMIT = "bench.submit"


@dataclasses.dataclass
class ProgramSummary:
    spans: dict[str, list[float]]   # rar.* seconds by name, window starts
    batches: list[dict]             # rar.batch: start_s, seconds, spans, tags
    idle_by_program: dict[str, float]  # idle seconds, innermost rar.* span
    idle_in_batch_s: float          # idle seconds inside rar.batch spans


def _host_events(planes):
    """(name, start_ns, end_ns, tags) of every host event, and the host
    launches of device programs (sorted)."""
    events, launches = [], []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name == TR.HOST_LAUNCH:
                    launches.append(s)
                elif ev.name.startswith((PREFIX, "bench.")):
                    tags = dict(getattr(ev, "stats", ()) or ())
                    events.append((ev.name, s, s + float(ev.duration_ns),
                                   tags))
    launches.sort()
    return events, launches


def _device_gaps(planes, launches, w0, w1):
    """Idle intervals of each device inside [w0, w1] on the host's clock,
    cut exactly as :func:`bench.trace.reduce_planes` cuts them."""
    gaps = []
    for p in planes:
        if not TR._DEVICE.match(p.name):
            continue
        ops, modules = [], []
        for line in p.lines:
            if line.name in TR.OP_LINES:
                ops.extend((s, e) for _, s, e in TR._events(line))
            elif line.name == TR.MODULE_LINE:
                modules.extend(s for _, s, _ in TR._events(line))
        shift = TR._clock_shift(modules, launches)
        clipped = [(max(s - shift, w0), min(e - shift, w1))
                   for s, e in ops]
        clipped = [(s, e) for s, e in clipped if e > s]
        if not clipped:
            continue
        edges = [w0] + [x for iv in TR._union(clipped) for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    return gaps


def _split(gaps, cover):
    """Cut ``gaps`` by the sorted disjoint intervals ``cover``: the parts
    inside it and the parts outside."""
    starts = [s for s, _ in cover]
    inside, outside = [], []
    for s, e in gaps:
        t = s
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(cover) and cover[i][0] < e:
            a, b = cover[i]
            if b > t:
                if a > t:
                    outside.append((t, a))
                inside.append((max(a, t), min(b, e)))
                t = min(b, e)
            i += 1
        if t < e:
            outside.append((t, e))
    return inside, outside


def reduce_planes(planes, in_flight=()) -> ProgramSummary:
    """``planes`` as for :func:`bench.trace.reduce_planes`; ``in_flight``
    the same (start, end) seconds from the window span's start in which
    the program held requests, for the labels outside ``rar.*`` spans."""
    events, launches = _host_events(planes)
    wins = [e for e in events if e[0] == TR.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {TR.WINDOW_SPAN} span")
    w0, w1 = min(e[1] for e in wins), max(e[2] for e in wins)
    rar = [e for e in events if e[0].startswith(PREFIX)
           and e[2] > w0 and e[1] < w1]
    bench = [e[:3] for e in events if e[0].startswith("bench.")
             and e[0] != TR.WINDOW_SPAN and e[2] > w0 and e[1] < w1]
    serve = [("bench.serve", w0 + a * 1e9, w0 + b * 1e9)
             for a, b in in_flight]

    spans: dict[str, list[float]] = {}
    for name, s, e, _ in rar:
        if s >= w0:
            spans.setdefault(name, []).append((e - s) * 1e-9)
    starts = sorted(s for _, s, _, _ in rar)
    batches = []
    for name, s, e, tags in sorted(rar, key=lambda x: x[1]):
        if name == BATCH and s >= w0:
            n = bisect.bisect_right(starts, e) - bisect.bisect_left(starts, s)
            batches.append(dict(tags, start_s=(s - w0) * 1e-9,
                                seconds=(e - s) * 1e-9, spans=n))

    gaps = _device_gaps(planes, launches, w0, w1)
    inside, outside = _split(gaps, TR._union([e[1:3] for e in rar]))
    idle = TR._name_gaps(outside, bench + serve)
    for k, v in TR._name_gaps(inside, [e[:3] for e in rar]).items():
        idle[k] = idle.get(k, 0.0) + v
    in_batch, _ = _split(gaps, TR._union(
        [e[1:3] for e in rar if e[0] == BATCH]))
    return ProgramSummary(
        spans=spans, batches=batches, idle_by_program=idle,
        idle_in_batch_s=sum(e - s for s, e in in_batch) * 1e-9)


def in_flight(planes, window) -> list:
    """The harness's ``in_flight`` for a run's ``window`` records: each
    microbatch from its submit to its resolve, in seconds from the window
    span's start. The host clock is placed on the trace's by pairing the
    microbatches' submit times, in order, with the ``bench.submit``
    spans that the window's profile holds."""
    events, _ = _host_events(planes)
    wins = [e for e in events if e[0] == TR.WINDOW_SPAN]
    if not wins:
        return []
    w0 = min(e[1] for e in wins)
    submits = sorted(e[1] for e in events if e[0] == SUBMIT and e[1] >= w0)
    held = {}
    for w in window:
        if w["submitted"] is not None:
            held.setdefault(w["submitted"], w["resolved"])
    pairs = list(zip(sorted(held), submits))
    if not pairs:
        return []
    t_span = statistics.median(sub - (ns - w0) * 1e-9 for sub, ns in pairs)
    return [(sub - t_span, res - t_span) for sub, res in sorted(held.items())
            if res is not None]


def summary_line(prog: ProgramSummary, top: int = 12) -> dict:
    """What a traced run reports of the program's spans: idle seconds by
    innermost span (``idle_gaps_program``), the share of idle inside
    ``rar.batch`` that a child span names, spans per microbatch, and
    each span's count and total seconds."""
    idle = sorted(prog.idle_by_program.items(), key=lambda kv: -kv[1])
    own = prog.idle_by_program.get(BATCH, 0.0)
    per = [b["spans"] for b in prog.batches]
    return {
        "idle_gaps_program": [[k, v] for k, v in idle[:top]],
        "idle_in_batch_s": prog.idle_in_batch_s,
        "idle_in_batch_named_share": (1.0 - own / prog.idle_in_batch_s
                                      if prog.idle_in_batch_s > 0 else None),
        "batches": len(per),
        "spans_per_batch_mean": sum(per) / len(per) if per else None,
        "spans_per_batch_max": max(per) if per else None,
        "spans": {k: [len(v), sum(v)] for k, v in sorted(prog.spans.items())},
    }


def of(ctx):
    """The program-span reduction of the run behind a metric's ``ctx``:
    computed once from the run's profile and kept on ``ctx`` as
    ``ctx.program``. None for an untraced run, or where no profile is
    left to read."""
    prog = getattr(ctx, "program", None)
    if prog is not None or ctx.trace is None:
        return prog
    import jax

    from bench.harness import TRACE_DIR
    t0 = time.monotonic()
    try:
        path = TR.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    profile = jax.profiler.ProfileData.from_file(path)
    planes = list(profile.planes)
    prog = reduce_planes(planes, in_flight(planes, ctx.window))
    ctx.program = prog
    line = summary_line(prog)
    line["reduce_s"] = time.monotonic() - t0
    print(f"[program] {json.dumps(line)}", file=sys.stderr, flush=True)
    return prog
