"""Reduction of one profiler trace to the numbers the per-layer metrics
read: device busy time inside the traced window, per-operation device
time, the device events of named kernels, and the idle gaps named by
the benchmark span the host was in (``bench.*``) during each gap.

The traced window is the host span ``bench.window``. Busy time is the
union of the operation intervals on a device's ``XLA Ops`` line, clipped
to the window, averaged over the devices that ran anything. Operations
are named by their HLO instruction without its number
(``%fusion.12 = ...`` is ``fusion``).

The device's clock in the trace runs up to about a millisecond apart
from the host's. Each device's times are shifted so that its programs
(``XLA Modules``) start no earlier than the nearest host launch of a
program (``PJRT_LoadedExecutable_Execute``), see :func:`_clock_shift`.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
HOST_LAUNCH = "PJRT_LoadedExecutable_Execute"
_OP_NAME = re.compile(r"^%?([^ =]+?)(\.\d+)?( =|$)")


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    m = _OP_NAME.match(hlo)
    return m.group(1) if m else hlo[:64]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    op_seconds: dict[str, float]         # per operation name, all devices
    kernel_events: dict[str, list[float]]  # per kernel pattern: seconds
    idle_by_host: dict[str, float]       # idle seconds by host span
    host_spans: dict[str, list[float]]   # bench.* span durations


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def _clock_shift(modules: list[float], launches: list[float]) -> float:
    """Device minus host clock. A program cannot start on the device
    before the host launches it, so this is a low quantile (the tenth
    percentile, robust to a launch matched wrongly) of the distance from
    each program's start to the nearest host launch."""
    if not modules or not launches:
        return 0.0
    import bisect
    d = []
    for m in modules:
        i = bisect.bisect_left(launches, m)
        near = [launches[j] for j in (i - 1, i) if 0 <= j < len(launches)]
        d.append(m - min(near, key=lambda h: abs(h - m)))
    d.sort()
    return d[len(d) // 10]


def _name_gaps(gaps, spans) -> dict[str, float]:
    """Idle seconds by the innermost benchmark span open on the host: each
    gap is cut where a span starts or ends, and each piece goes to the
    shortest span that covers it (``no_request`` where none does)."""
    import bisect
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    pieces = []
    for s, e in gaps:
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        edges = [s] + inner + [e]
        pieces.extend(zip(edges[:-1], edges[1:]))
    pieces.sort()
    spans = sorted(spans, key=lambda x: x[1])
    idle: dict[str, float] = {}
    active, nxt = [], 0
    for s, e in pieces:
        mid = (s + e) / 2
        while nxt < len(spans) and spans[nxt][1] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [x for x in active if x[2] > mid]
        label = min(active, key=lambda x: x[2] - x[1])[0] if active \
            else "no_request"
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
    return idle


def reduce_planes(planes, kernels: dict[str, str],
                  in_flight=()) -> Summary:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` of ``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them. ``kernels`` maps a label to
    a regular expression over device operation names. ``in_flight``:
    (start, end) host-clock seconds in which the program held requests,
    placed on the trace's clock by the ``bench.window`` span (its start
    is second 0); idle time inside them and no other span is named
    ``bench.serve``, idle time outside them ``no_request``."""
    host, launches, device_ops = [], [], []
    for p in planes:
        if _DEVICE.match(p.name):
            ops, modules = [], []
            for line in p.lines:
                if line.name in OP_LINES:
                    ops.extend(_events(line))
                elif line.name == MODULE_LINE:
                    modules.extend(s for _, s, _ in _events(line))
            device_ops.append((ops, modules))
        elif p.name.startswith("/host:"):
            for line in p.lines:
                for e in _events(line):
                    if e[0].startswith("bench."):
                        host.append(e)
                    elif e[0] == HOST_LAUNCH:
                        launches.append(e[1])
    launches.sort()
    wins = [e for e in host if e[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0, w1 = min(e[1] for e in wins), max(e[2] for e in wins)
    spans = [e for e in host if e[0] != WINDOW_SPAN and e[2] > w0
             and e[1] < w1]
    serve = [("bench.serve", w0 + a * 1e9, w0 + b * 1e9)
             for a, b in in_flight]
    host_spans: dict[str, list[float]] = {}
    for name, s, e in spans:
        host_spans.setdefault(name, []).append((e - s) * 1e-9)

    pats = {k: re.compile(v) for k, v in kernels.items()}
    op_seconds: dict[str, float] = {}
    kernel_events: dict[str, list[float]] = {k: [] for k in kernels}
    busy, gaps = [], []
    for evs, modules in device_ops:
        shift = _clock_shift(modules, launches)
        clipped = []
        for name, s, e in evs:
            s, e = max(s - shift, w0), min(e - shift, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            short = op_name(name)
            op_seconds[short] = op_seconds.get(short, 0.0) + (e - s) * 1e-9
            for k, pat in pats.items():
                if pat.search(name):
                    kernel_events[k].append((e - s) * 1e-9)
        if not clipped:
            continue
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    idle = _name_gaps(gaps, spans + serve)
    n = len(busy)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / n if n else 0.0, devices=n,
                   op_seconds=op_seconds, kernel_events=kernel_events,
                   idle_by_host=idle, host_spans=host_spans)


def reduce_file(path: str, kernels: dict[str, str],
                in_flight=()) -> Summary:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         kernels, in_flight)


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time, and idle time by what the host was doing."""
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
