"""Process-per-replica fabric: byte-identity to the threaded fabric,
cross-process commit broadcast, SIGKILL/hard-exit supervision with
redispatch, lease-expiry detection of hung workers, stale-completion
dedup, ticket timeout re-registration across the process boundary, and
full-state crash recovery (whole-fabric kill + manifest recover()).

Worker factories live at module level so the ``spawn`` start method can
re-import them inside the child processes.
"""
import functools
import os
import time

import numpy as np
import pytest
from test_fabric import build_fabric, serve_fabric
from test_pipeline import MEM_FIELDS, make_stream
from test_rar_controller import FakeTier, greq, make_cfg, prompt, skill_emb

from repro.serving.faults import FaultPlan, FaultSpec, random_plan
from repro.serving.procfabric import ProcessServingFabric, WorkerDied


# ---------------------------------------------------------------------------
# Picklable worker factory (spawn re-imports this module in the child)
# ---------------------------------------------------------------------------


class _CountEngine:
    """Minimal engine-counter object speaking the export/restore protocol
    — lets worker-side FakeTier calls ship across the process boundary as
    deltas and survive manifest recovery."""

    def __init__(self):
        self.calls = 0
        self.tokens_processed = 0

    def export_counters(self):
        return {"calls": self.calls,
                "tokens_processed": self.tokens_processed}

    def restore_counters(self, c):
        self.calls = c["calls"]
        self.tokens_processed = c["tokens_processed"]


def _no_embed(p):
    return None


def _route_false(emb, key):
    return False


def _make_parts(weak_known=()):
    weak = FakeTier(known=weak_known, name="weak")
    strong = FakeTier(known=range(10_000), can_guide=True, name="strong")
    weak.engine = _CountEngine()
    strong.engine = _CountEngine()
    return {"weak": weak, "strong": strong, "embed_fn": _no_embed,
            "route_weak_fn": _route_false}


def build_proc(workers=1, weak_known=(), fault_plan=None,
               lease_interval=0.25, lease_timeout=10.0, **cfg_kw):
    factory = functools.partial(_make_parts, tuple(sorted(weak_known)))
    return ProcessServingFabric(factory, make_cfg(**cfg_kw),
                                workers=workers, fault_plan=fault_plan,
                                lease_interval=lease_interval,
                                lease_timeout=lease_timeout)


def serve_proc(fab, stream, batch):
    """Serve ``stream`` serialized (wait out each ticket before the next
    submit) — the byte-identity path: admission order == serve order ==
    drain order, on any worker count."""
    outs = []
    for start in range(0, len(stream), batch):
        chunk = stream[start:start + batch]
        t = fab.submit([prompt(s, x) for s, x in chunk],
                       [greq(s) for s, _ in chunk], keys=chunk,
                       embs=np.stack([skill_emb(s) for s, _ in chunk]))
        outs += t.wait(timeout=180)
    fab.flush_shadow(timeout=180)
    return outs


def one(fab, skill, x, replica=None):
    """Submit a single-request microbatch and wait it out."""
    t = fab.submit([prompt(skill, x)], [greq(skill)], keys=[(skill, x)],
                   embs=np.stack([skill_emb(skill)]), replica=replica)
    return t.wait(timeout=180)[0]


def _calls(fab, name):
    """A fabric's total FM calls for one tier: through ``engine_calls``
    on the process fabric (serve calls live in shipped worker deltas),
    directly off the shared tier on the threaded one."""
    if hasattr(fab, "engine_calls"):
        return fab.engine_calls(name)
    tier = {"weak": fab.learn.weak, "strong": fab.learn.strong}[name]
    return tier.engine.calls


def assert_proc_equivalent(ref, ref_outs, fab, outs):
    """``test_shadow.assert_equivalent``, adapted to the process fabric's
    split call accounting."""
    assert ref_outs == outs
    for f in MEM_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ref.memory, f)),
                                      np.asarray(getattr(fab.memory, f)),
                                      f)
    assert ref.now == fab.now
    assert _calls(ref, "weak") == _calls(fab, "weak")
    assert _calls(ref, "strong") == _calls(fab, "strong")
    assert ref.guides_from_memory == fab.guides_from_memory
    assert ref.guides_generated == fab.guides_generated


# ---------------------------------------------------------------------------
# Equivalence: process fabric ≡ threaded fabric, byte for byte
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [dict(weak_known={0, 1}),
                                dict(weak_known=set())])
def test_one_worker_proc_fabric_identical_to_thread_fabric(kw):
    """The acceptance anchor: dispatch through a real worker *process*
    (pickle transport, epoch broadcasts, done-message funnels) must
    produce the same bytes as the in-process fabric — Outcome stream,
    memory state, FM-call totals, RQ2 counters."""
    stream = make_stream()
    ref = build_fabric(1, **kw)
    ref_outs = serve_fabric(ref, stream, 4)
    fab = build_proc(1, **kw)
    outs = serve_proc(fab, stream, 4)
    assert_proc_equivalent(ref, ref_outs, fab, outs)
    assert fab.stats()["transport"]["frames_sent"] > 0
    ref.close_shadow()
    fab.close_shadow()


def test_two_worker_proc_fabric_serialized_identical():
    """Round-robin across two worker processes, serialized: FIFO channel
    ordering guarantees each worker applies every prior drain epoch
    before its next serve, so the bytes cannot differ from one worker."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_fabric(1, **kw)
    ref_outs = serve_fabric(ref, stream, 4)
    fab = build_proc(2, **kw)
    outs = serve_proc(fab, stream, 4)
    assert_proc_equivalent(ref, ref_outs, fab, outs)
    ref.close_shadow()
    fab.close_shadow()


def test_pipelined_submission_identical_to_serialized():
    """Submit every microbatch up front (deep queue, zero waits): the
    worker's drain-ack gate enforces serve-after-drain, so routing is
    byte-identical to the paced one-ticket-at-a-time run. Without the
    gate a worker would serve a repeat skill against a mirror that has
    not yet applied the first occurrence's commit — routing, and the
    strong-call bill, silently diverge under deep pipelining."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_fabric(1, **kw)
    ref_outs = serve_fabric(ref, stream, 4)
    fab = build_proc(1, **kw)
    tickets = []
    for start in range(0, len(stream), 4):
        chunk = stream[start:start + 4]
        tickets.append(fab.submit(
            [prompt(s, x) for s, x in chunk],
            [greq(s) for s, _ in chunk], keys=chunk,
            embs=np.stack([skill_emb(s) for s, _ in chunk])))
    outs = []
    for t in tickets:
        outs += t.wait(timeout=180)
    fab.flush_shadow(timeout=180)
    assert_proc_equivalent(ref, ref_outs, fab, outs)
    ref.close_shadow()
    fab.close_shadow()


def test_epoch_broadcast_reaches_idle_worker():
    """A worker that never served still learns: pin every serve to
    worker 0, then a repeat skill pinned to worker 1 must route off the
    broadcast store view with zero strong calls."""
    fab = build_proc(2, weak_known={0})
    o1 = one(fab, 0, 1, replica=0)
    assert o1.case == "case1"
    o2 = one(fab, 0, 2, replica=1)
    assert o2.case == "memory_skill" and o2.strong_calls == 0
    assert o2.response == (0 + 2) % 4
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Supervision: SIGKILL / hard-exit / hung-worker detection + redispatch
# ---------------------------------------------------------------------------


def test_sigkill_mid_run_redispatch_byte_identical():
    """SIGKILL one worker process as it picks up a microbatch: EOF
    detection, respawn against the current store, and redispatch with
    the same pre-allocated stamps keep the run byte-identical to a
    no-fault one."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_fabric(1, **kw)
    ref_outs = serve_fabric(ref, stream, 4)
    plan = FaultPlan([FaultPlan.replica_kill(1, at=2)])
    fab = build_proc(2, fault_plan=plan, **kw)
    outs = serve_proc(fab, stream, 4)
    assert_proc_equivalent(ref, ref_outs, fab, outs)
    assert fab.deaths == 1 and fab.restarts == 1
    assert fab.redispatches == 1
    assert fab.stats()["health"] == ["healthy", "healthy"]
    ref.close_shadow()
    fab.close_shadow()


def test_worker_hard_exit_redispatch_byte_identical():
    """The "crash" action makes the worker process hard-exit (no
    cleanup, no farewell message) — same EOF + redispatch path as
    SIGKILL, same bytes."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_fabric(1, **kw)
    ref_outs = serve_fabric(ref, stream, 4)
    plan = FaultPlan([FaultPlan.replica_crash(0, at=1)])
    fab = build_proc(1, fault_plan=plan, **kw)
    outs = serve_proc(fab, stream, 4)
    assert_proc_equivalent(ref, ref_outs, fab, outs)
    assert fab.deaths == 1 and fab.restarts == 1
    assert fab.redispatches == 1
    ref.close_shadow()
    fab.close_shadow()


def test_redispatch_budget_exhausted_surfaces_worker_died():
    """With ``max_redispatch=0`` a worker death surfaces as
    :class:`WorkerDied` at the ticket — and the respawned worker keeps
    the fabric serviceable."""
    plan = FaultPlan([FaultPlan.replica_kill(0, at=1)])
    fab = build_proc(1, fault_plan=plan, weak_known={0, 1},
                     max_redispatch=0)
    t = fab.submit([prompt(0, 1)], [greq(0)], keys=[(0, 1)],
                   embs=np.stack([skill_emb(0)]))
    with pytest.raises(RuntimeError) as ei:
        t.wait(timeout=180)
    assert isinstance(ei.value.__cause__, WorkerDied)
    with pytest.raises(RuntimeError):
        fab.join(timeout=180)          # the barrier surfaces it too
    assert fab.deaths == 1 and fab.restarts == 1
    assert fab.redispatches == 0
    o = one(fab, 0, 2)                 # respawned worker serves
    assert o.case == "case1"
    fab.close_shadow()


def test_lease_expiry_detects_hung_worker():
    """A worker whose heartbeat thread dies (but which keeps serving) is
    exactly the failure EOF cannot see: the lease monitor must declare
    it dead and respawn the slot."""
    plan = FaultPlan([FaultPlan.heartbeat_crash(0, at=1)])
    fab = build_proc(1, fault_plan=plan, weak_known={0, 1},
                     lease_interval=0.1, lease_timeout=0.8)
    o = one(fab, 0, 1)
    assert o.case == "case1"
    deadline = time.monotonic() + 30
    # the monitor counts the expiry before it respawns the slot: wait
    # out the respawn too, or the check races it
    while (fab.lease_expiries == 0 or fab.restarts == 0) and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    assert fab.lease_expiries >= 1
    assert fab.deaths == 1 and fab.restarts == 1
    o2 = one(fab, 0, 2)                # respawned worker serves
    assert o2.case == "memory_skill"
    fab.close_shadow()


def test_injected_clock_skew_expires_lease_without_waiting():
    """Seeded clock skew advances the monitor's view of time: a healthy,
    beating worker's lease expires purely from the skew — the
    deterministic form of the wall-clock hang test."""
    plan = FaultPlan([FaultPlan.clock_skew(3600.0, at=1)])
    fab = build_proc(1, fault_plan=plan, weak_known={0, 1},
                     lease_interval=0.2, lease_timeout=60.0)
    deadline = time.monotonic() + 30
    while fab.lease_expiries == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert fab.lease_expiries == 1 and fab.deaths == 1
    o = one(fab, 0, 1)                 # the respawned slot serves fine
    assert o.case == "case1"
    fab.close_shadow()


def test_stale_done_is_dropped_not_double_applied():
    """A completion for a dispatch id the supervisor already
    redispatched must be dropped: a ticket is never resolved twice and a
    batch's authoritative effects land at most once."""
    fab = build_proc(1, weak_known={0, 1})
    before = fab.learn.shadow.items_enqueued
    fab._on_done(fab._handles[0], 999_999, [], [], [], {})
    assert fab.stale_drops == 1
    assert fab.learn.shadow.items_enqueued == before
    o = one(fab, 0, 1)                 # fabric unaffected
    assert o.case == "case1"
    fab.close_shadow()


def test_app_error_in_worker_surfaces_without_redispatch():
    """An application exception inside a worker's serve ships back
    verbatim and is NOT redispatched (its side effects may have landed)
    — parity with the threaded fabric."""
    plan = FaultPlan([FaultSpec("replica_serve", "error",
                                (("replica", 0),), at=1)])
    fab = build_proc(1, fault_plan=plan, weak_known={0, 1})
    t = fab.submit([prompt(0, 1)], [greq(0)], keys=[(0, 1)],
                   embs=np.stack([skill_emb(0)]))
    with pytest.raises(RuntimeError):
        t.wait(timeout=180)
    with pytest.raises(RuntimeError):
        fab.join(timeout=180)
    assert fab.deaths == 0 and fab.redispatches == 0
    o = one(fab, 0, 2)                 # same worker process, still alive
    assert o.case == "case1"
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Ticket timeout re-registration across the process boundary
# ---------------------------------------------------------------------------


def test_ticket_timeout_stays_waitable_across_process_boundary():
    """A timed-out ``wait``/``join`` leaves the ticket fully waitable
    while the batch is in flight in the worker process — and its late
    completion resolves the ticket exactly once (no redispatch, no
    stale drop)."""
    plan = FaultPlan([FaultSpec("replica_serve", "delay",
                                (("replica", 0),), at=1, delay=2.0)])
    fab = build_proc(1, fault_plan=plan, weak_known={0, 1},
                     lease_interval=0.1, lease_timeout=30.0)
    t = fab.submit([prompt(0, 1)], [greq(0)], keys=[(0, 1)],
                   embs=np.stack([skill_emb(0)]))
    with pytest.raises(TimeoutError):
        t.wait(timeout=0.2)
    with pytest.raises(TimeoutError):
        fab.join(timeout=0.2)          # re-registers the ticket
    outs = t.wait(timeout=180)         # same ticket, still live
    assert len(outs) == 1 and outs[0].case == "case1"
    fab.join(timeout=180)              # the re-registered barrier clears
    assert fab.redispatches == 0 and fab.deaths == 0
    assert fab.stale_drops == 0
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Full-state crash recovery (whole-fabric kill + manifest recover)
# ---------------------------------------------------------------------------


def test_whole_fabric_kill_recovers_byte_identical(tmp_path):
    """Kill the entire journaled fabric after a committed epoch, rebuild
    on the same WAL path: the recovery manifest restores the clock, the
    RQ2 counters, the engine cost counters (parent AND shipped worker
    deltas) and the store — resumed serving is byte-identical to an
    unkilled run."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_proc(1, **kw)
    ref_outs = serve_proc(ref, stream, 4)

    path = str(tmp_path / "wal")
    fab = build_proc(1, journal_path=path, snapshot_every=3, **kw)
    outs = serve_proc(fab, stream[:8], 4)
    fab.kill()
    fab2 = build_proc(1, journal_path=path, snapshot_every=3, **kw)
    outs += serve_proc(fab2, stream[8:], 4)
    assert_proc_equivalent(ref, ref_outs, fab2, outs)
    assert fab2.commit_stream.buffer.entries_applied == \
        int(np.asarray(fab2.memory.ptr))
    ref.close_shadow()
    fab2.close_shadow()


def test_clean_shutdown_checkpoint_recovers_full_state(tmp_path):
    """``close_shadow`` journals a manifest checkpoint: a fabric
    rebuilt after a *clean* shutdown resumes with the exact clock,
    counters and store — serving the rest of the stream matches the
    continuous run byte for byte."""
    kw = dict(weak_known={0, 1})
    stream = make_stream()
    ref = build_proc(1, **kw)
    ref_outs = serve_proc(ref, stream, 4)

    path = str(tmp_path / "wal")
    fab = build_proc(1, journal_path=path, snapshot_every=3, **kw)
    outs = serve_proc(fab, stream[:8], 4)
    fab.close_shadow()
    fab2 = build_proc(1, journal_path=path, snapshot_every=3, **kw)
    outs += serve_proc(fab2, stream[8:], 4)
    assert_proc_equivalent(ref, ref_outs, fab2, outs)
    ref.close_shadow()
    fab2.close_shadow()


# ---------------------------------------------------------------------------
# Soak: seeded SIGKILL + wire jitter + clock skew
# ---------------------------------------------------------------------------


def test_proc_soak_random_kills_jitter_and_skew():
    """Randomized (but seed-reproducible) schedule of process SIGKILLs,
    transport latency jitter and lease clock skew against a pipelined
    request stream. Invariants: every outcome resolves, deaths ==
    restarts, the applied-entries counter matches the ring pointer, and
    no completion is double-applied."""
    seed = int(os.environ.get("REPRO_SOAK_SEED", "0"))
    plan = random_plan(seed, replicas=2, kills=2, transport_delays=2,
                      clock_skews=2, max_jitter=0.03, horizon=12)
    fab = build_proc(2, fault_plan=plan, weak_known={0, 1},
                     lease_interval=0.1, lease_timeout=8.0)
    rng = np.random.default_rng(seed)
    tickets, total = [], 0
    for _ in range(14):
        chunk = [(int(rng.integers(0, 8)), int(rng.integers(0, 8)))
                 for _ in range(int(rng.integers(1, 4)))]
        total += len(chunk)
        tickets.append(fab.submit(
            [prompt(s, x) for s, x in chunk],
            [greq(s) for s, _ in chunk], keys=chunk,
            embs=np.stack([skill_emb(s) for s, _ in chunk])))
    fab.flush_shadow(timeout=300)
    outs = []
    for t in tickets:
        outs += t.wait(timeout=180)
    # at most 2 kills against a redispatch budget of 2 per ticket: every
    # microbatch must resolve
    assert len(outs) == total
    assert all(o.case for o in outs)
    assert fab.deaths == fab.restarts
    assert fab.commit_stream.buffer.entries_applied == \
        int(np.asarray(fab.memory.ptr))
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Construction validation
# ---------------------------------------------------------------------------


def test_constructor_validation():
    factory = functools.partial(_make_parts, ())
    with pytest.raises(ValueError, match="workers"):
        ProcessServingFabric(factory, make_cfg(), workers=0)
    with pytest.raises(ValueError, match="lease_timeout"):
        ProcessServingFabric(factory, make_cfg(), workers=1,
                             lease_interval=1.0, lease_timeout=0.5)


def test_refuses_accelerator_backend(monkeypatch):
    """A chip belongs to one process, so on an accelerator the process
    transport refuses to start (pointing at the thread fabric) before it
    builds a tier or spawns a worker."""
    import jax

    def factory():
        raise AssertionError("no tier may be built")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="thread"):
        ProcessServingFabric(factory, make_cfg(), workers=1)


# ---------------------------------------------------------------------------
# Parent learn-plane drain cadence driven by worker commit-epoch lag
# ---------------------------------------------------------------------------


def test_epoch_lag_drain_policy_decisions():
    """Unit semantics of the lag-aware cadence: empty queue never
    drains; lag 0 drains eagerly (broadcast plane idle); lag at/above
    the defer threshold holds; in between it falls through to the
    adaptive cost model (cold start: drain)."""
    from types import SimpleNamespace

    from repro.serving.procfabric import EpochLagDrainPolicy

    lag = {"v": 0}
    pol = EpochLagDrainPolicy(lambda: lag["v"], defer_lag=4)
    q = SimpleNamespace(_items=[], _batches=0, items_coalesced=0,
                        items_drained=0)
    pol.register(q)
    assert pol.due() is False                 # nothing pending
    q._items = [1, 2]
    assert pol.due() is True                  # lag 0: eager
    assert pol.lag_eager_drains == 1
    lag["v"] = 4
    assert pol.due() is False                 # backed up: defer
    assert pol.lag_deferrals == 1
    lag["v"] = 2
    assert pol.due() is True                  # mid lag: cost model,
    assert pol.coldstart_drains == 1          # cold start drains
    s = pol.stats()
    assert s["worker_epoch_lag"] == 2
    assert s["defer_lag"] == 4
    assert s["lag_eager_drains"] == 1 and s["lag_deferrals"] == 1
    with pytest.raises(ValueError):
        EpochLagDrainPolicy(lambda: 0, defer_lag=0)


def test_proc_adaptive_mode_installs_epoch_lag_policy():
    """``shadow_mode="adaptive"`` on the process fabric wires the
    parent learn queue to the lag-aware policy (heartbeat epochs, not
    just pending count), serving stays exact, and the barrier leaves
    nothing pending."""
    from repro.serving.procfabric import EpochLagDrainPolicy

    fab = build_proc(2, weak_known={0, 1}, shadow_mode="adaptive",
                     shadow_flush_every=4)
    try:
        assert isinstance(fab.drain_policy, EpochLagDrainPolicy)
        assert fab.learn.shadow.drain_policy is fab.drain_policy
        stream = make_stream()
        ref, ref_outs = None, None
        outs = serve_proc(fab, stream, 4)
        assert all(o.case for o in outs)
        assert len(outs) == len(stream)
        learn = fab.metrics()["replicas"][0]
        assert learn["items_enqueued"] == learn["items_drained"]
        pol = fab.metrics()["drain_policy"]
        assert pol["decisions"] > 0
        assert "worker_epoch_lag" in pol and "lag_eager_drains" in pol
    finally:
        fab.close_shadow()


def test_proc_adaptive_identical_to_thread_adaptive_outcomes():
    """The cadence signal changes *when* drains happen, never what they
    produce: adaptive process fabric matches the threaded closed-loop
    reference byte-for-byte at the barrier."""
    stream = make_stream()
    ref = build_fabric(1, weak_known={0, 1})
    ref_outs = serve_fabric(ref, stream, 4, submit=True)
    fab = build_proc(1, weak_known={0, 1}, shadow_mode="adaptive",
                     shadow_flush_every=4)
    try:
        outs = serve_proc(fab, stream, 4)
        assert_proc_equivalent(ref, ref_outs, fab, outs)
    finally:
        fab.close_shadow()
        ref.close_shadow()
