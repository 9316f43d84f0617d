"""The program-span reduction (``bench/program_trace.py``) on hand-made
planes and on the TPU fixture, and the readers of the four metrics it
feeds on hand-built contexts."""
import dataclasses
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench import program_trace as PT
from bench import spec as S
from bench import trace as TR

FIXTURE = Path(__file__).parent / "fixtures" / "trace.xplane.pb"


@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


def _ev(name, a, b, **tags):
    return _Ev(name, a, b - a, tuple(tags.items()))


HOST = [_ev("bench.window", 0, 1000), _ev("bench.submit", 20, 30),
        _ev("rar.batch", 100, 900, batch=4, wait_us=1500, syncs=7),
        _ev("rar.embed", 100, 200), _ev("bench.embed", 120, 180),
        _ev("rar.strong", 300, 600), _ev("bench.tier.strong", 310, 590),
        _ev("rar.engine.fetch", 400, 500), _ev("rar.decide", 650, 700)]
DEVICE = _Plane("/device:TPU:0", [_Line("XLA Ops", [
    _ev("fusion.1", 200, 300), _ev("fusion.2", 420, 480),
    _ev("fusion.3", 700, 800)])])
IN_FLIGHT = [(10e-9, 950e-9)]


def _planes(host):
    return [_Plane("/host:CPU", [_Line("main", host)]), DEVICE]


def test_idle_named_by_the_innermost_program_span():
    prog = PT.reduce_planes(_planes(HOST), IN_FLIGHT)
    want = {"rar.embed": 100, "rar.strong": 200, "rar.engine.fetch": 40,
            "rar.batch": 150, "rar.decide": 50, "bench.submit": 10,
            "bench.serve": 130, "no_request": 60}
    assert set(prog.idle_by_program) == set(want)
    for k, v in want.items():
        assert prog.idle_by_program[k] == pytest.approx(v * 1e-9), k
    # every idle second is named once: the old reduction's total
    old = TR.reduce_planes(_planes(HOST), {}, IN_FLIGHT)
    assert sum(prog.idle_by_program.values()) == pytest.approx(
        old.window_s - old.busy_s)
    assert prog.idle_in_batch_s == pytest.approx(540e-9)
    (b,) = prog.batches
    assert (b["batch"], b["wait_us"], b["syncs"], b["spans"]) == \
        (4, 1500, 7, 5)
    assert b["seconds"] == pytest.approx(800e-9)
    assert prog.spans["rar.strong"] == [pytest.approx(300e-9)]
    line = PT.summary_line(prog)
    assert line["idle_gaps_program"][0][0] == "rar.strong"
    assert line["idle_in_batch_named_share"] == pytest.approx(
        1 - 150 / 540)
    assert line["spans_per_batch_max"] == 5


def test_program_spans_leave_the_old_reduction_alone():
    """The benchmark's own reduction ignores ``rar.*`` spans: with or
    without them it names idle time by the ``bench.*`` probes alone."""
    bare = [e for e in HOST if not e.name.startswith("rar.")]
    a = TR.reduce_planes(_planes(HOST), {}, IN_FLIGHT)
    b = TR.reduce_planes(_planes(bare), {}, IN_FLIGHT)
    assert a == b
    prog = PT.reduce_planes(_planes(bare), IN_FLIGHT)
    assert prog.batches == [] and prog.spans == {}
    assert prog.idle_by_program == pytest.approx(b.idle_by_host)


@pytest.fixture(scope="module")
def fixture_planes():
    import jax
    return list(jax.profiler.ProfileData.from_file(str(FIXTURE)).planes)


def test_tpu_fixture_unchanged_by_program_spans(fixture_planes):
    """On the trace recorded on a TPU v5e: added ``rar.*`` spans change
    no number of the old reduction, and where there are none the
    program's reduction names idle time exactly as the old one does."""
    old = TR.reduce_planes(fixture_planes, {"topk": "topk"})
    host, w0 = next((p, ev.start_ns) for p in fixture_planes
                    if p.name.startswith("/host:")
                    for line in p.lines for ev in line.events
                    if ev.name == TR.WINDOW_SPAN)
    extra = _Line("rar", [_ev("rar.batch", w0, w0 + 4e6, batch=0),
                          _ev("rar.decide", w0 + 1e6, w0 + 2e6)])
    planes = [_Plane(p.name, list(p.lines) + [extra]) if p is host else p
              for p in fixture_planes]
    new = TR.reduce_planes(planes, {"topk": "topk"})
    assert new.busy_s == old.busy_s
    assert new.op_seconds == old.op_seconds
    assert new.idle_by_host == old.idle_by_host
    prog = PT.reduce_planes(fixture_planes)
    assert prog.batches == []
    assert prog.idle_by_program == pytest.approx(old.idle_by_host,
                                                 rel=1e-9)
    prog = PT.reduce_planes(planes)
    assert len(prog.batches) == 1 and prog.idle_by_program["rar.decide"] > 0
    assert sum(prog.idle_by_program.values()) == pytest.approx(
        old.window_s - old.busy_s, rel=1e-9)


def test_in_flight_placed_by_the_submit_spans():
    host = [_ev("bench.window", 1000, 2000), _ev("bench.submit", 1100, 1110),
            _ev("bench.submit", 1500, 1510)]
    t_span = 100.0
    window = [{"submitted": t_span + 100e-9, "resolved": t_span + 800e-9},
              {"submitted": t_span + 100e-9, "resolved": t_span + 800e-9},
              {"submitted": t_span + 500e-9, "resolved": t_span + 900e-9},
              {"submitted": None, "resolved": None}]
    got = PT.in_flight(_planes(host), window)
    assert np.allclose(got, [(100e-9, 800e-9), (500e-9, 900e-9)],
                       atol=1e-12)


def _ctx(program=None):
    ctx = harness.Context(config={}, seconds=1.0, window=[], calls=[],
                          embeds=[], drain_s=[], trace=None, peak={},
                          prompt_len=8)
    if program is not None:
        ctx.program = program
    return ctx


PROGRAM = PT.ProgramSummary(
    spans={"rar.decide": [0.001, 0.002, 0.003],
           "rar.commit": [0.004, 0.006], "rar.batch": [0.1, 0.2]},
    batches=[{"wait_us": 1000, "syncs": 10}, {"wait_us": 3000, "syncs": 13}],
    idle_by_program={}, idle_in_batch_s=0.0)
EMPTY = PT.ProgramSummary(spans={}, batches=[], idle_by_program={},
                          idle_in_batch_s=0.0)
READINGS = {"replica_wait_ms_p95": float(np.percentile([1.0, 3.0], 95)),
            "decide_ms": 3.0, "commit_ms": 5.0, "syncs_per_batch": 11.5}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_program_metric_readers(name):
    read = S.metric_reader(name)
    assert read(_ctx(PROGRAM)) == pytest.approx(READINGS[name])
    assert read(_ctx()) is None            # untraced: no program spans
    assert read(_ctx(EMPTY)) is None       # a program without the spans
