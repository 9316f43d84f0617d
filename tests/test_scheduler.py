"""Continuous-batching admission scheduler: loadgen determinism, batch-
formation properties (no drop / no within-stream reorder / atomic batch
dispatch / SLO budgets / bucket purity), and the determinism pin — the
same seeded arrival trace through the batcher yields byte-identical
routing decisions and strong-call counts to a closed-loop reference
run, for any ``slo_ms``, any priority mix, on both the threaded and the
process fabric. Plus one seeded open-loop soak with a mid-run worker
kill (the chaos-job entry point)."""
import os

import numpy as np
import pytest
from test_fabric import build_fabric
from test_procfabric import build_proc, _calls
from test_rar_controller import greq, prompt, skill_emb

from repro.serving.loadgen import (ArrivalEvent, bursty_trace,
                                   poisson_trace, trace_replay)
from repro.serving.metrics import MetricsRegistry
from repro.serving.scheduler import ContinuousBatcher, Request, serve_trace


# ---------------------------------------------------------------------------
# Load generator
# ---------------------------------------------------------------------------


def test_poisson_trace_deterministic_and_well_formed():
    a = poisson_trace(40, 25.0, seed=7, streams=4, priorities=[0, 1],
                      deadline_ms=80.0)
    b = poisson_trace(40, 25.0, seed=7, streams=4, priorities=[0, 1],
                      deadline_ms=80.0)
    assert a == b                                   # same seed, same bytes
    assert a != poisson_trace(40, 25.0, seed=8, streams=4,
                              priorities=[0, 1], deadline_ms=80.0)
    assert [e.index for e in a] == list(range(40))
    assert all(a[i].t <= a[i + 1].t for i in range(len(a) - 1))
    assert all(e.t > 0 for e in a)
    # round-robin split of an int total, priorities cycled per stream
    per = [sum(1 for e in a if e.stream == j) for j in range(4)]
    assert per == [10, 10, 10, 10]
    assert all(e.priority == e.stream % 2 for e in a)
    assert all(e.deadline_ms == 80.0 for e in a)


def test_poisson_trace_per_stream_counts_and_rates():
    t = poisson_trace([3, 5], 10.0, seed=0, streams=2, rates=[5.0, 50.0])
    assert [sum(1 for e in t if e.stream == j) for j in range(2)] == [3, 5]
    # rate is an honest long-run mean: high-rate stream finishes sooner
    big = poisson_trace([500, 500], 10.0, seed=1, streams=2,
                        rates=[5.0, 50.0])
    last = [max(e.t for e in big if e.stream == j) for j in range(2)]
    assert last[1] < last[0]


def test_bursty_trace_mean_rate_preserved_and_clustered():
    n, rate = 2000, 100.0
    t = bursty_trace(n, rate, seed=3, burst=3.0, duty=0.25)
    assert t == bursty_trace(n, rate, seed=3, burst=3.0, duty=0.25)
    span = t[-1].t
    realized = n / span
    assert 0.8 * rate < realized < 1.25 * rate      # thinning keeps the mean
    # burstiness: inter-arrival squared-CV well above the Poisson 1.0
    gaps = np.diff([e.t for e in t])
    cv2 = float(np.var(gaps) / np.mean(gaps) ** 2)
    assert cv2 > 1.2


def test_bursty_trace_rejects_impossible_duty_cycle():
    with pytest.raises(ValueError):
        bursty_trace(10, 5.0, burst=5.0, duty=0.5)  # burst*duty > 1


def test_trace_replay_normalises_and_validates():
    r = trace_replay([(0.5, 1), {"t": 0.1, "stream": 0, "priority": 2,
                                 "deadline_ms": 9.0},
                      ArrivalEvent(t=0.3, stream=2)])
    assert [e.t for e in r] == [0.1, 0.3, 0.5]
    assert [e.index for e in r] == [0, 1, 2]
    assert r[0].priority == 2 and r[0].deadline_ms == 9.0
    assert r[2].stream == 1
    with pytest.raises(ValueError):
        trace_replay([(-1.0, 0)])


# ---------------------------------------------------------------------------
# Batch-formation properties (recording fake fabric — no controller)
# ---------------------------------------------------------------------------


class _FakeTicket:
    def __init__(self, n):
        self.n = n

    def wait(self, timeout=None):
        return [None] * self.n


class _FakeFabric:
    """Records every submit; enough surface for the batcher."""

    replicas = [0, 1]

    def __init__(self):
        self.submits = []

    def submit(self, prompts, guide_requests, keys=None, embs=None,
               replica=None):
        self.submits.append({"keys": list(keys), "replica": replica,
                             "lens": [len(p) for p in prompts]})
        return _FakeTicket(len(prompts))


def _random_requests(rng, n, streams, lengths=(3,), deadline_frac=0.0):
    reqs, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(0.01))
        reqs.append(Request(
            arrival_s=t, stream=int(rng.integers(0, streams)),
            prompt=[0] * int(rng.choice(lengths)), guide_request=None,
            priority=int(rng.integers(0, 3)),
            deadline_ms=(float(rng.uniform(5, 50))
                         if rng.random() < deadline_frac else None),
            key=i, index=i))
    return reqs


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("slo_ms", [None, 4.0, 40.0])
def test_batcher_properties_random_traces(seed, slo_ms):
    """For random traces at any SLO: every admitted request dispatches
    exactly once, batches respect the size cap and length buckets,
    within-stream dispatch preserves arrival order, a batch dispatches
    atomically at close (no late joiners), and no request overstays a
    finite queueing budget."""
    rng = np.random.default_rng(seed)
    fab = _FakeFabric()
    bat = ContinuousBatcher(fab, microbatch=4, slo_ms=slo_ms,
                            registry=MetricsRegistry())
    reqs = _random_requests(rng, 80, streams=3, lengths=(3, 5),
                            deadline_frac=0.25)
    for r in reqs:
        bat.admit(r)
    bat.flush()
    # no drop, no duplicate: every key dispatched exactly once
    dispatched = [k for s in fab.submits for k in s["keys"]]
    assert sorted(dispatched) == list(range(80))
    for s in fab.submits:
        assert 1 <= len(s["keys"]) <= 4             # size cap
        assert len(set(s["lens"])) == 1             # one length bucket
    # atomic close: a request's batch contains it when it dispatches,
    # and each batch id dispatches exactly once
    ids = [d.batch_id for d in bat.dispatches]
    assert len(ids) == len(set(ids))
    for d in bat.dispatches:
        assert all(r.batch_id == d.batch_id for r in d.requests)
    # within-stream order: dispatch sequence preserves arrival order
    for j in range(3):
        seq = [r.index for d in bat.dispatches for r in d.requests
               if r.stream == j]
        assert seq == sorted(seq)
    # a stream's requests always target the same replica
    for j in range(3):
        assert len({d.replica for d in bat.dispatches
                    for r in d.requests if r.stream == j}) == 1
    # budget respected: dispatch never breaches a finite queueing budget
    for d in bat.dispatches:
        for r in d.requests:
            budget = (r.deadline_ms / 1e3 if r.deadline_ms is not None
                      else (slo_ms / 1e3) / (1 + r.priority)
                      if slo_ms is not None else float("inf"))
            assert r.dispatch_s - r.arrival_s <= budget + 1e-9


def test_slo_close_fires_at_the_oldest_members_deadline():
    fab = _FakeFabric()
    bat = ContinuousBatcher(fab, microbatch=8, slo_ms=20.0,
                            registry=MetricsRegistry())
    bat.admit(Request(arrival_s=0.0, stream=0, prompt=[0] * 3,
                      guide_request=None, key=0, index=0))
    bat.admit(Request(arrival_s=0.015, stream=0, prompt=[0] * 3,
                      guide_request=None, key=1, index=1))
    # nothing due yet; the next arrival pushes the clock past 20 ms
    assert not fab.submits
    bat.admit(Request(arrival_s=0.05, stream=0, prompt=[0] * 3,
                      guide_request=None, key=2, index=2))
    assert len(fab.submits) == 1
    assert fab.submits[0]["keys"] == [0, 1]
    d = bat.dispatches[0]
    assert d.reason == ContinuousBatcher.CLOSE_SLO
    assert d.dispatch_s == pytest.approx(0.020)     # oldest arrival + SLO
    assert bat.closes["slo"] == 1


@pytest.mark.parametrize("pace", [False, True])
def test_queue_delay_records_the_wall_wait_when_paced(pace):
    """A close that runs 0.5 s late: paced, ``sched/queue_delay_ms``
    holds the wall wait from the due instant to the submit (the budget
    plus the lateness); unpaced, the virtual wait (the budget alone)."""
    import time
    reg = MetricsRegistry()
    bat = ContinuousBatcher(_FakeFabric(), microbatch=8, slo_ms=20.0,
                            registry=reg, pace=pace)
    bat._t0_wall = time.monotonic() - 0.5     # the arrival was 0.5 s ago
    bat.admit(Request(arrival_s=0.0, stream=0, prompt=[0] * 3,
                      guide_request=None, key=0, index=0))
    bat.advance(0.03)
    h = reg.histogram("sched/queue_delay_ms")
    assert h.count == 1
    if pace:
        assert h.total >= 500.0
    else:
        assert h.total == pytest.approx(20.0)


def test_priority_tightens_the_queueing_budget():
    fab = _FakeFabric()
    bat = ContinuousBatcher(fab, microbatch=8, slo_ms=40.0,
                            registry=MetricsRegistry())
    bat.admit(Request(arrival_s=0.0, stream=0, prompt=[0] * 3,
                      guide_request=None, priority=3, key=0, index=0))
    bat.advance(0.011)                              # 40/(1+3) = 10 ms budget
    assert len(fab.submits) == 1
    assert bat.dispatches[0].dispatch_s == pytest.approx(0.010)


def test_bucket_switch_closes_the_streams_previous_batch():
    """Per-stream FIFO across buckets: when a stream's next request
    lands in a different length bucket, the batch holding its previous
    request dispatches first — a stream can never have two open batches
    in flight."""
    fab = _FakeFabric()
    bat = ContinuousBatcher(fab, microbatch=8, slo_ms=None,
                            registry=MetricsRegistry())
    bat.admit(Request(arrival_s=0.0, stream=0, prompt=[0] * 3,
                      guide_request=None, key=0, index=0))
    bat.admit(Request(arrival_s=0.001, stream=0, prompt=[0] * 7,
                      guide_request=None, key=1, index=1))
    assert len(fab.submits) == 1                    # short-prompt batch
    assert fab.submits[0]["keys"] == [0]
    assert bat.dispatches[0].reason == ContinuousBatcher.CLOSE_STREAM
    bat.flush()
    assert [s["keys"] for s in fab.submits] == [[0], [1]]


def test_admit_rejects_time_travel():
    bat = ContinuousBatcher(_FakeFabric(), microbatch=4,
                            registry=MetricsRegistry())
    bat.admit(Request(arrival_s=1.0, stream=0, prompt=[0] * 3,
                      guide_request=None, key=0, index=0))
    with pytest.raises(ValueError):
        bat.admit(Request(arrival_s=0.5, stream=0, prompt=[0] * 3,
                          guide_request=None, key=1, index=1))


# ---------------------------------------------------------------------------
# Determinism pin: open-loop ≡ closed-loop routing, thread + process
# ---------------------------------------------------------------------------
#
# Stream content mirrors the throughput bench's sharding: each stream
# owns a disjoint skill set (cross-stream retrieval can't interact) and
# repeats a skill only after a full round (repeats never share a
# microbatch) — under those conditions the batch partition is free to
# vary with slo_ms / priorities while routing stays byte-identical.


MICROBATCH = 4
ROUND_SKILLS = 6                       # > MICROBATCH: repeats can't collide


def _stream_seqs(streams, reps=3):
    """Per-stream (skill, x) sequences over disjoint skill sets."""
    return [[(j * ROUND_SKILLS + k, rep)
             for rep in range(reps) for k in range(ROUND_SKILLS)]
            for j in range(streams)]


def _serve_closed(fab, seqs, replicas):
    """Closed-loop reference: per-stream pre-partitioned microbatches,
    stream j pinned to replica j % replicas."""
    tickets = []
    for j, seq in enumerate(seqs):
        for start in range(0, len(seq), MICROBATCH):
            chunk = seq[start:start + MICROBATCH]
            tickets.append((j, fab.submit(
                [prompt(s, x) for s, x in chunk],
                [greq(s) for s, _ in chunk], keys=chunk,
                embs=np.stack([skill_emb(s) for s, _ in chunk]),
                replica=j % replicas)))
    fab.flush_shadow(timeout=180)
    by_stream = [[] for _ in seqs]
    for j, t in tickets:
        by_stream[j] += t.wait(timeout=180)
    return by_stream


def _serve_open(fab, seqs, trace, replicas, slo_ms):
    """Open-loop: the k-th arrival of stream j serves that stream's
    k-th request, admitted through the batcher."""
    cursors = [0] * len(seqs)
    admitted = []

    def make_request(ev):
        s, x = seqs[ev.stream][cursors[ev.stream]]
        cursors[ev.stream] += 1
        admitted.append(ev.stream)
        return prompt(s, x), greq(s), (s, x), skill_emb(s)

    outs, batcher = serve_trace(
        fab, trace, make_request, microbatch=MICROBATCH, slo_ms=slo_ms,
        replica_fn=lambda s: s % replicas, timeout=180)
    fab.flush_shadow(timeout=180)
    by_stream = [[] for _ in seqs]
    for j, out in zip(admitted, outs):
        by_stream[j].append(out)
    return by_stream, batcher


@pytest.mark.parametrize("slo_ms", [None, 3.0, 500.0])
@pytest.mark.parametrize("priorities", [None, [0, 2]])
def test_openloop_pin_thread_fabric(slo_ms, priorities):
    """Any slo_ms × priority mix: same seeded trace → per-stream
    Outcome streams and strong/weak call counts byte-identical to the
    closed-loop reference (formation changes, routing cannot)."""
    streams = replicas = 2
    seqs = _stream_seqs(streams)
    ref = build_fabric(replicas, weak_known={0, 1})
    ref_outs = _serve_closed(ref, seqs, replicas)
    trace = poisson_trace([len(s) for s in seqs], 300.0, seed=11,
                          streams=streams, priorities=priorities)
    fab = build_fabric(replicas, weak_known={0, 1})
    outs, batcher = _serve_open(fab, seqs, trace, replicas, slo_ms)
    assert outs == ref_outs
    assert fab.learn.weak.engine.calls == ref.learn.weak.engine.calls
    assert fab.learn.strong.engine.calls == ref.learn.strong.engine.calls
    assert batcher.stats()["dispatched"] == sum(len(s) for s in seqs)
    # latency accounting reached the fabric's shared registry
    snap = fab.metrics()["registry"]
    assert snap["sched/queue_delay_ms"]["count"] == \
        sum(len(s) for s in seqs)
    assert "sched/stream1/e2e_ms" in snap
    ref.close_shadow()
    fab.close_shadow()


def test_openloop_same_trace_same_bytes_across_runs():
    """Run-to-run determinism of the full open-loop path: identical
    trace, identical outcomes and batch partition."""
    streams = replicas = 2
    seqs = _stream_seqs(streams)
    runs = []
    for _ in range(2):
        fab = build_fabric(replicas, weak_known={0, 1})
        trace = bursty_trace([len(s) for s in seqs], 200.0, seed=5,
                             streams=streams)
        outs, batcher = _serve_open(fab, seqs, trace, replicas, 15.0)
        runs.append((outs, [d.batch_id for d in batcher.dispatches],
                     [len(d.requests) for d in batcher.dispatches],
                     batcher.closes.copy()))
        fab.close_shadow()
    assert runs[0] == runs[1]


@pytest.mark.parametrize("slo_ms", [None, 10.0])
def test_openloop_pin_process_fabric(slo_ms):
    """The same pin across the process boundary: open-loop through
    ``ProcessServingFabric`` matches the threaded closed-loop reference
    outcome-for-outcome and call-for-call."""
    streams = workers = 2
    seqs = _stream_seqs(streams, reps=2)
    ref = build_fabric(workers, weak_known={0, 1})
    ref_outs = _serve_closed(ref, seqs, workers)
    trace = poisson_trace([len(s) for s in seqs], 400.0, seed=23,
                          streams=streams, priorities=[0, 1])
    fab = build_proc(workers, weak_known={0, 1})
    try:
        outs, _ = _serve_open(fab, seqs, trace, workers, slo_ms)
        assert outs == ref_outs
        assert _calls(fab, "weak") == ref.learn.weak.engine.calls
        assert _calls(fab, "strong") == ref.learn.strong.engine.calls
    finally:
        fab.close_shadow()
        ref.close_shadow()


def test_openloop_soak_survives_worker_kill():
    """Chaos entry point: a seeded open-loop trace through the batcher
    with a mid-run SIGKILL of worker 1 — every request resolves, the
    outcomes match a kill-free run byte-for-byte (redispatch is exact),
    and supervision actually exercised (death + restart)."""
    from repro.serving.faults import FaultPlan
    seed = int(os.environ.get("REPRO_SOAK_SEED", "0"))
    streams = workers = 2
    seqs = _stream_seqs(streams, reps=2)
    trace = poisson_trace([len(s) for s in seqs], 250.0, seed=seed,
                          streams=streams)

    def run(fault_plan):
        fab = build_proc(workers, weak_known={0, 1},
                         fault_plan=fault_plan, lease_interval=0.1,
                         lease_timeout=8.0)
        try:
            outs, batcher = _serve_open(fab, seqs, trace, workers, 10.0)
            return outs, batcher.stats(), fab.deaths, fab.restarts
        finally:
            fab.close_shadow()

    clean_outs, clean_stats, _, _ = run(None)
    plan = FaultPlan([FaultPlan.replica_kill(1, at=2)])
    kill_outs, kill_stats, deaths, restarts = run(plan)
    assert kill_outs == clean_outs
    assert kill_stats == clean_stats        # formation is trace-only
    assert deaths >= 1 and deaths == restarts
    total = sum(len(s) for s in seqs)
    assert sum(len(o) for o in kill_outs) == total
