"""The trace reduction on a small trace recorded on a TPU v5e with
``record_trace.py``: three top-k reads through the program's kernel and
three matrix products inside the benchmark's spans."""
from pathlib import Path

import pytest

from bench import trace as TR

FIXTURE = Path(__file__).parent / "fixtures" / "trace.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return TR.reduce_file(str(FIXTURE), {"topk": "topk"})


def test_fixture_is_small():
    assert FIXTURE.stat().st_size < 1 << 20


def test_busy_within_window(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s < summary.window_s
    # the window holds three sleeps of 2 ms each way at least
    assert summary.window_s > 0.012


def test_kernel_events(summary):
    assert len(summary.kernel_events["topk"]) == 3
    assert all(s > 0 for s in summary.kernel_events["topk"])


def test_idle_named_by_host_span(summary):
    idle = summary.idle_by_host
    assert set(idle) <= {"bench.tier.weak", "bench.embed", "bench.submit",
                         "bench.tier.strong", "bench.serve",
                         "no_request"}
    assert idle.get("bench.embed", 0) > 0.004     # the embed sleeps
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_breakdown_shape(summary):
    b = TR.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
