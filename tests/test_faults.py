"""Fault-tolerance suite: deterministic fault injection, replica
supervision + redispatch byte-identity, tier retry/backoff/breaker,
degraded-mode (weak-only) routing with deferred probe replay, and
crash-consistent journal recovery.

The invariants pinned here are the recovery plane's acceptance criteria:

* a replica crash fires before any side effect, so a supervised
  redispatch run is byte-identical to a no-fault run;
* a kill before the WAL append recovers to the previous epoch, a kill
  after the WAL append (mid-apply) recovers one epoch ahead — never a
  torn epoch;
* a strong-tier brownout serves weak-only with zero errored tickets and
  replays every deferred probe once the breaker closes;
* with no FaultPlan and default config every path is byte-identical to
  the pre-resilience code (the existing equivalence suites run wrapped).
"""
import dataclasses
import os
import threading
import time

import jax
import numpy as np
import pytest
from test_pipeline import MEM_FIELDS, make_stream, run_batched
from test_rar_controller import FakeTier, greq, make_cfg, prompt, skill_emb
from test_shadow import assert_equivalent

from repro.core import memory as mem
from repro.core.decisions import DEGRADED_CASES
from repro.core.fm import (CircuitBreaker, InjectedTierError, ResilientTier,
                           RetryPolicy, TierTimeout, TierUnavailableError)
from repro.core.pipeline import MicrobatchRAR
from repro.core.rar import RAR, RARConfig, retry_policy
from repro.core.shadow import ShadowQueue
from repro.serving.fabric import ServingFabric, Ticket
from repro.serving.faults import (FaultPlan, FaultSpec, InjectedFault,
                                  ReplicaCrash, random_plan)


def build_fabric(replicas=1, weak_known=(), fault_plan=None, **cfg_kw):
    weak = FakeTier(known=weak_known, name="weak")
    strong = FakeTier(known=range(10_000), can_guide=True, name="strong")
    return ServingFabric(weak, strong, lambda p: None, lambda e, k: False,
                         make_cfg(**cfg_kw), replicas=replicas,
                         fault_plan=fault_plan)


def serve_serialized(fab, stream, batch):
    """Submit microbatches one ticket at a time (wait each before the
    next submit): the serve order is then deterministic even across a
    crash + redispatch, which is what makes byte-identity assertable."""
    outs = []
    for start in range(0, len(stream), batch):
        chunk = stream[start:start + batch]
        t = fab.submit([prompt(s, x) for s, x in chunk],
                       [greq(s) for s, _ in chunk], keys=chunk,
                       embs=np.stack([skill_emb(s) for s, _ in chunk]))
        outs += t.wait(timeout=60)
    fab.flush_shadow()
    return outs


# ---------------------------------------------------------------------------
# FaultPlan mechanics
# ---------------------------------------------------------------------------


def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec("nope", "crash")
    with pytest.raises(ValueError):
        FaultSpec("drain", "explode")
    with pytest.raises(ValueError):
        FaultSpec("drain", "error", at=0)


def test_fault_plan_fires_at_exact_hit_numbers():
    plan = FaultPlan([FaultPlan.replica_crash(1, at=2, count=2)])
    plan.fire("replica_serve", replica=1)          # hit 1: below `at`
    plan.fire("replica_serve", replica=0)          # other replica: no match
    for _ in range(2):                             # hits 2..3: due
        with pytest.raises(ReplicaCrash):
            plan.fire("replica_serve", replica=1)
    plan.fire("replica_serve", replica=1)          # hit 4: spent
    assert plan.n_fired == 2
    assert all(site == "replica_serve" for site, _, _ in plan.fired)


def test_fault_plan_reproducible_and_off_is_noop():
    def drive(plan):
        log = []
        for i in range(6):
            try:
                plan.fire("tier_call", tier="strong", op="answer_batch")
                log.append("ok")
            except InjectedTierError:
                log.append("err")
        return log

    a = drive(FaultPlan([FaultPlan.tier_error("strong", at=3, count=2)]))
    b = drive(FaultPlan([FaultPlan.tier_error("strong", at=3, count=2)]))
    assert a == b == ["ok", "ok", "err", "err", "ok", "ok"]
    assert drive(FaultPlan()) == ["ok"] * 6        # empty plan: no-op


def test_random_plan_is_seed_deterministic():
    kw = dict(replicas=3, crashes=2, tier_errors=2, drain_errors=1,
              wal_crashes=1, apply_crashes=1, kills=2,
              transport_delays=2, clock_skews=2, max_jitter=0.04)
    a, b = random_plan(7, **kw), random_plan(7, **kw)
    assert a.specs == b.specs
    c = random_plan(8, **kw)
    assert a.specs != c.specs
    # every requested fault family is present in the schedule
    assert {s.site for s in a.specs} == {
        "replica_serve", "tier_call", "drain", "wal_write",
        "commit_apply", "transport_frame", "clock_skew"}
    assert sum(s.action == "kill" for s in a.specs) == 2
    for s in a.specs:
        if s.site in ("transport_frame", "clock_skew"):
            assert 0.0 < s.delay <= 0.04


# ---------------------------------------------------------------------------
# Tier resilience: retry, backoff, timeout, breaker
# ---------------------------------------------------------------------------


def make_resilient(policy, plan=None, seed=1, **kw):
    inner = FakeTier(known=range(10_000), can_guide=True, name="strong")
    sleeps = []
    rt = ResilientTier(inner, policy, name="strong", fault_plan=plan,
                       seed=seed, sleep_fn=sleeps.append, **kw)
    return rt, inner, sleeps


def test_retry_recovers_from_transient_errors():
    plan = FaultPlan([FaultPlan.tier_error("strong", at=1, count=2)])
    rt, inner, slept = make_resilient(RetryPolicy(max_retries=3), plan)
    ans = rt.answer_batch([prompt(3, 1)])
    assert ans[0] == (3 + 1) % 4                  # succeeded on attempt 3
    assert rt.retries == 2 and rt.failures == 2
    assert slept == rt.sleeps and len(slept) == 2
    assert inner.engine.calls == 1                # failures fired pre-call


def test_retry_backoff_is_seeded_and_deterministic():
    def sleeps_for(seed):
        plan = FaultPlan([FaultPlan.tier_error("strong", count=3)])
        rt, _, slept = make_resilient(
            RetryPolicy(max_retries=3, backoff_base=0.1), plan, seed=seed)
        rt.answer_batch([prompt(0, 0)])
        return slept

    a, b = sleeps_for(5), sleeps_for(5)
    assert a == b and len(a) == 3
    # exponential envelope with jitter in [0.5, 1.5) of the base
    for i, s in enumerate(a):
        assert 0.5 * 0.1 * 2 ** i <= s < 1.5 * 0.1 * 2 ** i
    assert sleeps_for(6) != a


def test_exhausted_retries_raise_unavailable():
    plan = FaultPlan([FaultPlan.tier_error("strong", count=10)])
    rt, inner, _ = make_resilient(RetryPolicy(max_retries=2), plan)
    with pytest.raises(TierUnavailableError):
        rt.answer_batch([prompt(0, 0)])
    assert rt.failures == 3                       # 1 try + 2 retries
    assert inner.engine.calls == 0


def test_latency_spike_beyond_timeout_raises_tier_timeout():
    plan = FaultPlan([FaultPlan.tier_delay("strong", delay=30.0)])
    rt, _, _ = make_resilient(RetryPolicy(timeout=0.05), plan)
    with pytest.raises(TierUnavailableError) as ei:
        rt.answer_batch([prompt(0, 0)])
    assert isinstance(ei.value.__cause__, TierTimeout)  # and never slept


def test_wrapper_preserves_inner_capability_surface():
    rt, inner, _ = make_resilient(RetryPolicy())
    assert getattr(rt, "answer_many", None) is None   # FakeTier lacks it
    assert rt.engine is inner.engine
    assert rt.name == "strong"
    with pytest.raises(AttributeError):
        rt.no_such_method


def test_circuit_breaker_lifecycle():
    clock = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=10.0, now_fn=lambda: clock[0])
    assert br.state == "closed" and br.available()
    br.record_failure()
    assert br.state == "closed"                   # below threshold
    br.record_failure()
    assert br.state == "open" and br.opens == 1
    assert not br.available()
    with pytest.raises(TierUnavailableError):     # cooling: calls shed
        br.before_call()
    assert br.shed == 1
    clock[0] = 11.0                               # cooldown elapsed
    assert br.available()
    br.before_call()                              # half-open probe slot
    assert br.state == "half_open"
    with pytest.raises(TierUnavailableError):     # single probe at a time
        br.before_call()
    br.record_success()
    assert br.state == "closed" and br.available()


def test_breaker_reopens_on_failed_probe():
    clock = [0.0]
    br = CircuitBreaker(threshold=1, cooldown=5.0, now_fn=lambda: clock[0])
    br.record_failure()
    clock[0] = 6.0
    br.before_call()                              # half-open probe
    br.record_failure()                           # probe failed
    assert br.state == "open" and br.opens == 2
    assert not br.available()


def test_adaptive_breaker_tightens_threshold_and_stretches_cooldown():
    """A flaky call history drives the error EWMA up: the breaker opens
    after fewer consecutive failures and cools down longer — all under
    an injected clock, no wall time involved."""
    clock = [0.0]
    br = CircuitBreaker(threshold=4, cooldown=10.0,
                        now_fn=lambda: clock[0], adaptive=True,
                        ewma_alpha=0.5)
    # clean history: effective knobs are exactly the configured ones
    st = br.stats()
    assert st["error_ewma"] == 0.0
    assert st["effective_threshold"] == 4
    assert st["effective_cooldown"] == 10.0
    br.record_failure()          # ewma .50 → effective threshold 2
    assert br.state == "closed"
    assert br.stats()["effective_threshold"] == 2
    br.record_failure()          # ewma .75 → threshold 1 ≤ 2 failures
    assert br.state == "open" and br.opens == 1
    st = br.stats()
    assert st["error_ewma"] == pytest.approx(0.75)
    assert st["effective_cooldown"] == pytest.approx(17.5)
    clock[0] = 10.5              # static cooldown elapsed — adaptive not
    assert not br.available()
    clock[0] = 17.5
    assert br.available()


def test_adaptive_breaker_relaxes_back_on_successes():
    """Successes decay the EWMA: after a clean stretch the effective
    knobs return to (approach) the configured ones."""
    clock = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=10.0,
                        now_fn=lambda: clock[0], adaptive=True,
                        ewma_alpha=1.0)  # memoryless: tracks last call
    br.record_failure()          # ewma 1.0 → threshold 1: trips at once
    assert br.state == "open" and br.opens == 1
    clock[0] = 20.0              # effective cooldown = 10 · (1 + 1)
    assert br.available()
    br.before_call()             # half-open probe
    br.record_success()
    assert br.state == "closed"
    st = br.stats()
    assert st["error_ewma"] == 0.0
    assert st["effective_threshold"] == 2
    assert st["effective_cooldown"] == 10.0


def test_adaptive_breaker_default_off_keeps_static_knobs():
    """adaptive=False (the default): the EWMA never moves and the
    effective knobs are the static ones, whatever the history — the
    byte-identity pins over the static breaker hold unchanged."""
    clock = [0.0]
    br = CircuitBreaker(threshold=3, cooldown=5.0,
                        now_fn=lambda: clock[0])
    br.record_failure()
    br.record_failure()
    assert br.error_ewma == 0.0 and br.state == "closed"
    assert br._effective_threshold_locked() == 3
    assert br._effective_cooldown_locked() == 5.0
    assert "error_ewma" not in br.stats()         # off ⇒ not advertised
    with pytest.raises(ValueError):
        CircuitBreaker(threshold=1, cooldown=1.0, ewma_alpha=0.0)
    with pytest.raises(ValueError):
        CircuitBreaker(threshold=1, cooldown=1.0, ewma_alpha=1.5)


def test_adaptive_breaker_state_survives_export_restore():
    """The manifest round-trip carries the learned error rate: a
    recovered site resumes with the dead site's EWMA, not a clean
    slate."""
    clock = [0.0]
    br = CircuitBreaker(threshold=4, cooldown=10.0,
                        now_fn=lambda: clock[0], adaptive=True,
                        ewma_alpha=0.5)
    br.record_failure()
    br.record_failure()          # open, ewma .75
    st = br.export_state()
    assert st["error_ewma"] == pytest.approx(0.75)
    br2 = CircuitBreaker(threshold=4, cooldown=10.0,
                         now_fn=lambda: clock[0], adaptive=True,
                         ewma_alpha=0.5)
    br2.restore_state(st)
    assert br2.state == "open" and br2.opens == 1
    assert br2.stats()["error_ewma"] == pytest.approx(0.75)
    assert br2.stats()["effective_cooldown"] == pytest.approx(17.5)
    legacy = dict(st)
    legacy.pop("error_ewma")     # manifest from a pre-adaptive build
    br2.restore_state(legacy)
    assert br2.error_ewma == 0.0


def test_adaptive_knobs_flow_through_config():
    """RARConfig → retry_policy → ResilientTier plumbing, plus config
    validation of the smoothing factor."""
    rt, _, _ = make_resilient(RetryPolicy(breaker_threshold=3,
                                          breaker_adaptive=True,
                                          breaker_ewma_alpha=0.5))
    assert rt.breaker.adaptive and rt.breaker.ewma_alpha == 0.5
    cfg = make_cfg(breaker_threshold=2, breaker_adaptive=True,
                   breaker_ewma_alpha=0.3)
    pol = retry_policy(cfg)
    assert pol.breaker_adaptive and pol.breaker_ewma_alpha == 0.3
    with pytest.raises(ValueError):
        make_cfg(breaker_ewma_alpha=0.0)


def test_default_policy_wrapper_is_pass_through():
    """With every knob off the wrapper adds nothing: same answers, same
    engine-call counts, exceptions propagate untouched."""
    rt, inner, slept = make_resilient(RetryPolicy())
    ref = FakeTier(known=range(10_000), can_guide=True)
    ps = [prompt(s, x) for s in range(4) for x in range(2)]
    np.testing.assert_array_equal(rt.answer_batch(ps), ref.answer_batch(ps))
    np.testing.assert_array_equal(rt.generate_guides([greq(1)], 8),
                                  ref.generate_guides([greq(1)], 8))
    assert inner.engine.calls == ref.engine.calls
    assert rt.breaker is None and not slept


# ---------------------------------------------------------------------------
# Degraded-mode routing: brownout → weak-only, deferred probes replay
# ---------------------------------------------------------------------------


def brownout_cfg(**kw):
    base = dict(tier_max_retries=0, breaker_threshold=1,
                breaker_cooldown=0.05)
    base.update(kw)
    return make_cfg(**base)


def test_sequential_brownout_serves_weak_only_and_replays():
    plan = FaultPlan([FaultPlan.tier_error("strong", at=1, count=1)])
    holder = {}
    rar = RAR(FakeTier(known={5, 6}, name="weak"),
              FakeTier(known=range(10_000), can_guide=True, name="strong"),
              lambda p: holder["emb"], lambda e, k: False,
              brownout_cfg(), fault_plan=plan)

    def go(s, x):
        holder["emb"] = skill_emb(s)
        return rar.process(prompt(s, x), greq(s), key=(s, x))

    out = go(5, 1)                     # strong call fails → probe deferred
    assert out.case == "shadow_deferred" and out.served_by == "weak"
    assert out.strong_calls == 0 and out.response == (5 + 1) % 4
    assert rar.probes_deferred == 1 and len(rar.deferred_probes) == 1
    out2 = go(6, 2)                    # breaker open → routed degraded
    assert out2.case == "shadow_deferred" and out2.strong_calls == 0
    assert rar.probes_deferred == 2
    assert rar.memory_occupancy == 0   # nothing recorded during brownout

    time.sleep(0.08)                   # breaker cooldown elapses
    assert rar.replay_deferred() == 2
    assert rar.probes_replayed == 2 and not rar.deferred_probes
    # the deferred outcomes resolved in place: probe ran, entry recorded
    assert out.case == "case1" and out.strong_calls == 1
    assert out.served_by == "weak"     # the user-facing serve is history
    assert rar.memory_occupancy == 2
    # and the memory now routes the skill without the strong tier
    out3 = go(5, 3)
    assert out3.case == "memory_skill" and out3.strong_calls == 0


def test_sequential_brownout_memory_hard_degraded():
    """A hard entry hit during a brownout serves weak-only (no strong
    fallback, no re-probe while the tier is down) and the cool-down
    clock keeps running."""
    # go(3,1) makes two strong calls (answer + guide gen); hit 4 is the
    # strong fallback of the SECOND memory-hard hit
    plan = FaultPlan([FaultPlan.tier_error("strong", at=4, count=1)])
    holder = {}
    weak = FakeTier(known=set(), name="weak")
    weak.answer_batch = lambda ps: np.asarray([-1] * len(ps))  # stubborn
    rar = RAR(weak,
              FakeTier(known=range(10_000), can_guide=True, name="strong"),
              lambda p: holder["emb"], lambda e, k: False,
              brownout_cfg(reprobe_period=100), fault_plan=plan)

    def go(s, x):
        holder["emb"] = skill_emb(s)
        return rar.process(prompt(s, x), greq(s), key=(s, x))

    assert go(3, 1).case == "case3"    # hard entry lands (2 strong calls)
    assert go(3, 2).case == "memory_hard"
    out = go(3, 3)                     # 3rd strong call injected → breaker
    # the hit is within cool-down; with the strong tier down the serve
    # degrades to the weak answer instead of erroring
    assert out.case == "memory_hard_degraded" and out.served_by == "weak"
    assert out.strong_calls == 0
    assert rar.probes_deferred == 0    # hard hits defer nothing


@pytest.mark.parametrize("batch", [1, 4])
def test_batched_brownout_weak_only_and_flush_replays(batch):
    plan = FaultPlan([FaultPlan.tier_error("strong", at=1, count=1)])
    ctrl = MicrobatchRAR(
        FakeTier(known={0, 1}, name="weak"),
        FakeTier(known=range(10_000), can_guide=True, name="strong"),
        lambda p: None, lambda e, k: False, brownout_cfg(),
        fault_plan=plan)
    stream = make_stream()
    outs = []
    for start in range(0, len(stream), batch):
        chunk = stream[start:start + batch]
        outs += ctrl.process_batch(
            [prompt(s, x) for s, x in chunk],
            [greq(s) for s, _ in chunk], keys=chunk,
            embs=np.stack([skill_emb(s) for s, _ in chunk]))
    # zero errors: every request served (weak-only where degraded)
    assert len(outs) == len(stream)
    assert all(o.response is not None for o in outs)
    degraded = [o for o in outs if o.case in DEGRADED_CASES]
    assert degraded and all(o.strong_calls == 0 for o in degraded)
    assert ctrl.probes_deferred == len(ctrl.deferred_probes) > 0
    time.sleep(0.08)
    ctrl.flush_shadow()                # barrier replays deferred probes
    assert ctrl.probes_replayed == ctrl.probes_deferred
    assert not ctrl.deferred_probes
    assert all(o.case not in ("shadow_deferred",) for o in outs)
    assert ctrl.shadow.items_enqueued == ctrl.shadow.items_drained
    ctrl.close_shadow()


def test_fabric_brownout_zero_errored_tickets():
    plan = FaultPlan([FaultPlan.tier_error("strong", at=1, count=1)])
    fab = build_fabric(2, weak_known={0, 1}, fault_plan=plan,
                       tier_max_retries=0, breaker_threshold=1,
                       breaker_cooldown=0.05)
    assert isinstance(fab.learn.strong, ResilientTier)
    # one shared wrapper across replicas: an outage seen by one degrades
    # routing on all
    assert all(r.strong is fab.learn.strong for r in fab.replicas)
    outs = serve_serialized(fab, make_stream(), 4)
    assert all(o.response is not None for o in outs)   # no errored tickets
    stats = fab.stats()
    assert stats["probes_deferred"] > 0
    assert stats["strong_resilience"]["failures"] == 1
    time.sleep(0.08)
    fab.flush_shadow()                 # replay once the breaker closes
    stats = fab.stats()
    assert stats["probes_replayed"] == stats["probes_deferred"]
    assert fab.learn.strong.breaker.state == "closed"
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Replica supervision: crash → restart + redispatch, byte-identical
# ---------------------------------------------------------------------------


def test_replica_crash_redispatch_byte_identical_to_no_fault_run():
    """The acceptance anchor: a crashed worker's microbatch redispatches
    to a survivor and the run's bytes (outcomes, memory, FM calls, RQ2
    counters) match the no-fault run exactly."""
    stream = make_stream()
    ref, ref_outs = run_batched(stream, 4, weak_known={0, 1})
    plan = FaultPlan([FaultPlan.replica_crash(0, at=2)])
    fab = build_fabric(2, weak_known={0, 1}, fault_plan=plan)
    fab_outs = serve_serialized(fab, stream, 4)
    assert_equivalent(ref, ref_outs, fab.learn, fab_outs)
    assert fab.deaths == 1 and fab.restarts == 1 and fab.redispatches == 1
    assert fab.health == ["healthy", "healthy"]
    fab.close_shadow()


def test_single_replica_crash_restarts_and_recovers():
    """A 1-replica fabric redispatches to its own restarted slot."""
    stream = make_stream()
    ref, ref_outs = run_batched(stream, 4, weak_known={0, 1})
    plan = FaultPlan([FaultPlan.replica_crash(0, at=3)])
    fab = build_fabric(1, weak_known={0, 1}, fault_plan=plan)
    fab_outs = serve_serialized(fab, stream, 4)
    assert_equivalent(ref, ref_outs, fab.learn, fab_outs)
    assert fab.deaths == 1 and fab.restarts == 1
    fab.close_shadow()


def test_bounded_redispatch_exhaustion_surfaces_crash():
    plan = FaultPlan([FaultPlan.replica_crash(0, count=100),
                      FaultPlan.replica_crash(1, count=100)])
    fab = build_fabric(2, weak_known={0}, fault_plan=plan,
                       max_redispatch=2)
    t = fab.submit([prompt(0, 1)], [greq(0)], embs=skill_emb(0)[None],
                   replica=0)
    with pytest.raises(RuntimeError) as ei:
        t.wait(timeout=60)
    assert isinstance(ei.value.__cause__, ReplicaCrash)
    assert t.redispatches == 2                     # bounded: 1 try + 2 re
    assert fab.deaths == 3 and fab.restarts == 3
    # clear the join barrier of the failed ticket, then verify the
    # restarted workers still serve (the crash specs are spent)
    with pytest.raises(RuntimeError):
        fab.join()
    plan.specs.clear()
    outs = serve_serialized(fab, [(0, 2), (1, 3)], 2)
    assert len(outs) == 2
    fab.close_shadow()


def test_app_level_error_is_not_redispatched():
    """Only ReplicaCrash is redispatchable: an application exception's
    batch may already have side effects, so it must surface as before
    (pins the pre-existing worker-error contract)."""
    fab = build_fabric(2, weak_known={0})
    boom = RuntimeError("app bug")

    def dying(prompts):
        raise boom

    fab.replicas[1].strong = FakeTier(known=range(10_000), can_guide=True)
    fab.replicas[1].strong.answer_batch = dying
    t = fab.submit([prompt(5, 1)], [greq(5)], embs=skill_emb(5)[None],
                   replica=1)
    with pytest.raises(RuntimeError) as ei:
        t.wait(timeout=60)
    assert ei.value.__cause__ is boom
    assert t.redispatches == 0 and fab.redispatches == 0
    with pytest.raises(RuntimeError):
        fab.join()
    fab.close_shadow()


# ---------------------------------------------------------------------------
# Bounded barriers + ticket semantics (satellites)
# ---------------------------------------------------------------------------


def test_ticket_wait_timeout_then_still_waitable():
    t = Ticket(replica=0)
    with pytest.raises(TimeoutError):
        t.wait(timeout=0.01)
    t.outcomes = ["ok"]
    t._done.set()
    assert t.wait(timeout=1) == ["ok"]            # timed-out wait ≠ abandoned


def test_ticket_wait_chains_worker_error():
    t = Ticket(replica=3)
    cause = ValueError("inner")
    t.error = cause
    t._done.set()
    with pytest.raises(RuntimeError) as ei:
        t.wait()
    assert ei.value.__cause__ is cause
    assert "replica 3" in str(ei.value)


def test_fabric_join_timeout_keeps_tickets_registered():
    fab = build_fabric(1, weak_known={0})
    gate = threading.Event()
    orig = fab.replicas[0].process_batch

    def gated(*a, **kw):
        gate.wait()
        return orig(*a, **kw)

    fab.replicas[0].process_batch = gated
    fab.submit([prompt(0, 1)], [greq(0)], embs=skill_emb(0)[None])
    with pytest.raises(TimeoutError):
        fab.join(timeout=0.05)
    assert fab._tickets                            # re-registered, retryable
    gate.set()
    fab.join(timeout=60)                           # retry succeeds
    with pytest.raises(TimeoutError):
        # flush_shadow passes its bound through the join leg
        fab.replicas[0].process_batch = gated
        gate.clear()
        fab.submit([prompt(0, 2)], [greq(0)], embs=skill_emb(0)[None])
        fab.flush_shadow(timeout=0.05)
    gate.set()
    fab.flush_shadow(timeout=60)
    fab.close_shadow()


def test_shadow_close_raises_on_wedged_drainer_instead_of_orphaning():
    """The PR-4 bug fix: close() used to null the worker reference even
    when join timed out, orphaning a live drainer that could still write
    to the store. Now the barrier failure raises and the handle is kept
    so the caller can retry."""
    release = threading.Event()

    def slow_runner(items):
        release.wait()

    q = ShadowQueue(slow_runner, mode="async", flush_every=1)
    q.submit([None])                               # wakes the drainer
    with pytest.raises(TimeoutError):
        q.close(timeout=0.05)
    assert q._worker is not None                   # NOT orphaned
    release.set()
    q.close(timeout=60)                            # retry completes
    assert q._worker is None


def test_injected_drain_error_surfaces_at_barrier():
    plan = FaultPlan([FaultPlan.drain_error(at=1)])
    ctrl = MicrobatchRAR(
        FakeTier(known=set(), name="weak"),
        FakeTier(known=range(10_000), can_guide=True, name="strong"),
        lambda p: None, lambda e, k: False,
        make_cfg(shadow_mode="async", shadow_flush_every=1),
        fault_plan=plan)
    ctrl.process_batch([prompt(2, 1)], [greq(2)],
                       embs=skill_emb(2)[None])
    with pytest.raises(RuntimeError, match="shadow drainer failed"):
        for _ in range(100):
            ctrl.flush_shadow()
            time.sleep(0.01)
    ctrl.close_shadow()


# ---------------------------------------------------------------------------
# Crash-consistent journal: WAL + snapshot recovery
# ---------------------------------------------------------------------------


def run_journaled(stream, path, fault_plan=None, snapshot_every=8,
                  **cfg_kw):
    holder = {}
    rar = RAR(FakeTier(known={0, 1}, name="weak"),
              FakeTier(known=range(10_000), can_guide=True, name="strong"),
              lambda p: holder["emb"], lambda e, k: False,
              make_cfg(journal_path=path, snapshot_every=snapshot_every,
                       **cfg_kw),
              fault_plan=fault_plan)
    # host copies of the store after each commit epoch: the stream
    # updates its store in place, so a device reference goes stale
    snapshots = {0: jax.device_get(rar.memory)}
    for s, x in stream:
        holder["emb"] = skill_emb(s)
        rar.process(prompt(s, x), greq(s), key=(s, x))
        snapshots[rar.commit_stream.buffer.epoch] = \
            jax.device_get(rar.memory)
    return rar, snapshots


def assert_states_equal(a, b):
    for f in MEM_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)


@pytest.mark.parametrize("snapshot_every", [1, 3, 100])
def test_journal_recovery_is_byte_identical(tmp_path, snapshot_every):
    """Clean-shutdown recovery: journal a run, recover from disk, get
    the exact same store — regardless of where the last snapshot fell
    (snapshot_every=100 → pure WAL replay; =1 → pure snapshot)."""
    path = str(tmp_path / "journal")
    rar, _ = run_journaled(make_stream(), path,
                           snapshot_every=snapshot_every)
    rec = mem.MemoryJournal.recover(path, rar.cfg.memory)
    assert rec is not None
    state, epoch, applied, _ = rec
    assert_states_equal(state, rar.memory)
    assert epoch == rar.commit_stream.buffer.epoch
    assert applied == rar.commit_stream.buffer.entries_applied


def test_journaled_run_matches_unjournaled_run(tmp_path):
    """Journaling is write-path-only: the served bytes are identical to
    the journal-off run."""
    stream = make_stream()
    holder = {}

    def build(**kw):
        return RAR(FakeTier(known={0, 1}, name="weak"),
                   FakeTier(known=range(10_000), can_guide=True,
                            name="strong"),
                   lambda p: holder["emb"], lambda e, k: False,
                   make_cfg(**kw))

    ref = build()
    jr = build(journal_path=str(tmp_path / "journal"))
    ref_outs, jr_outs = [], []
    for s, x in stream:
        holder["emb"] = skill_emb(s)
        ref_outs.append(ref.process(prompt(s, x), greq(s), key=(s, x)))
        holder["emb"] = skill_emb(s)
        jr_outs.append(jr.process(prompt(s, x), greq(s), key=(s, x)))
    assert_equivalent(ref, ref_outs, jr, jr_outs)


def test_wal_crash_recovers_previous_epoch(tmp_path):
    """Kill before the WAL record is durable → the in-flight epoch is
    lost, recovery lands exactly on the previous epoch's bytes."""
    path = str(tmp_path / "journal")
    crash_at = 4
    plan = FaultPlan([FaultPlan.wal_crash(at=crash_at)])
    with pytest.raises(InjectedFault):
        run_journaled(make_stream(), path, fault_plan=plan,
                      snapshot_every=100)
    _, ref_snapshots = run_journaled(make_stream(),
                                     str(tmp_path / "ref"),
                                     snapshot_every=100)
    state, epoch, _, _ = mem.MemoryJournal.recover(
        path, make_cfg().memory)
    assert epoch == crash_at - 1
    assert_states_equal(state, ref_snapshots[crash_at - 1])


def test_apply_crash_recovers_one_epoch_ahead(tmp_path):
    """Kill after the WAL record but before the in-memory apply → the
    journaled epoch survives the crash: recovery replays it and lands
    one epoch AHEAD of the crashed process's memory."""
    path = str(tmp_path / "journal")
    crash_at = 4
    plan = FaultPlan([FaultPlan.apply_crash(at=crash_at)])
    with pytest.raises(InjectedFault):
        run_journaled(make_stream(), path, fault_plan=plan,
                      snapshot_every=100)
    _, ref_snapshots = run_journaled(make_stream(),
                                     str(tmp_path / "ref"),
                                     snapshot_every=100)
    state, epoch, _, _ = mem.MemoryJournal.recover(path, make_cfg().memory)
    assert epoch == crash_at
    assert_states_equal(state, ref_snapshots[crash_at])


def test_recovery_tolerates_torn_wal_tail(tmp_path):
    path = str(tmp_path / "journal")
    rar, _ = run_journaled(make_stream(), path, snapshot_every=100)
    with open(os.path.join(path, "wal.log"), "ab") as f:
        f.write(b"\x07\x00\x00\x00garbage-torn-frame")  # power-cut tail
    with pytest.warns(mem.JournalCorruptionWarning, match="crc mismatch"):
        state, epoch, _, _ = mem.MemoryJournal.recover(path,
                                                       rar.cfg.memory)
    assert_states_equal(state, rar.memory)
    assert epoch == rar.commit_stream.buffer.epoch


def test_wal_bit_flip_stops_replay_at_corrupt_frame(tmp_path):
    """Bit rot mid-file: replay keeps every epoch before the flipped
    frame, drops everything at and after it, and says where and why in
    a structured warning — never a raised exception, never a torn
    store."""
    path = str(tmp_path / "journal")
    rar, snapshots = run_journaled(make_stream(), path, snapshot_every=100)
    wal = os.path.join(path, "wal.log")
    with open(wal, "rb") as f:
        data = bytearray(f.read())
    data[12] ^= 0x40                 # payload byte of the FIRST frame
    with open(wal, "wb") as f:
        f.write(bytes(data))
    with pytest.warns(mem.JournalCorruptionWarning) as rec:
        state, epoch, applied, _ = mem.MemoryJournal.recover(
            path, rar.cfg.memory)
    w = next(r.message for r in rec
             if isinstance(r.message, mem.JournalCorruptionWarning))
    assert w.path == wal and w.offset == 0 and w.reason == "crc mismatch"
    assert epoch == 0 and applied == 0      # no snapshot: nothing survives
    assert_states_equal(state, snapshots[0])


def test_wal_truncated_frame_recovers_prefix_with_warning(tmp_path):
    """Cut the file mid-frame (lost sector): recovery is exact up to
    the last intact frame and warns with the torn frame's offset."""
    path = str(tmp_path / "journal")
    rar, snapshots = run_journaled(make_stream(), path, snapshot_every=100)
    wal = os.path.join(path, "wal.log")
    with open(wal, "rb") as f:
        data = f.read()
    with open(wal, "wb") as f:
        f.write(data[:len(data) - 3])       # 3 bytes short of a frame
    with pytest.warns(mem.JournalCorruptionWarning,
                      match="torn payload") as rec:
        state, epoch, _, _ = mem.MemoryJournal.recover(path,
                                                       rar.cfg.memory)
    w = next(r.message for r in rec
             if isinstance(r.message, mem.JournalCorruptionWarning))
    assert w.offset > 0
    assert epoch == rar.commit_stream.buffer.epoch - 1
    assert_states_equal(state, snapshots[epoch])


def test_recovered_store_resumes_serving(tmp_path):
    """E2E restart: a new controller opened on the journal path starts
    from the recovered store and serves memory hits immediately — and
    keeps journaling (a second recovery sees the new epochs)."""
    path = str(tmp_path / "journal")
    stream = make_stream()
    rar, _ = run_journaled(stream, path, snapshot_every=3)
    epoch0 = rar.commit_stream.buffer.epoch
    occupancy0 = rar.memory_occupancy
    holder = {}
    rar2 = RAR(FakeTier(known={0, 1}, name="weak"),
               FakeTier(known=range(10_000), can_guide=True,
                        name="strong"),
               lambda p: holder["emb"], lambda e, k: False,
               make_cfg(journal_path=path, snapshot_every=3))
    assert_states_equal(rar2.memory, rar.memory)
    assert rar2.commit_stream.buffer.epoch == epoch0
    holder["emb"] = skill_emb(stream[0][0])       # a learned skill
    out = rar2.process(prompt(stream[0][0], 7), greq(stream[0][0]),
                       key=None)
    assert out.strong_calls == 0                  # memory hit, no relearn
    assert rar2.memory_occupancy == occupancy0
    # learn one new skill → new journal epoch → recoverable
    holder["emb"] = skill_emb(40)
    rar2.process(prompt(40, 1), greq(40), key=None)
    _, epoch2, _, _ = mem.MemoryJournal.recover(path, rar2.cfg.memory)
    assert epoch2 == rar2.commit_stream.buffer.epoch > epoch0


def test_sequential_manifest_restores_engine_state(tmp_path):
    """The WAL carries the controller's engine-state manifest inside
    every epoch frame (plus a manifest-only checkpoint frame at clean
    shutdown): reopening the journal path restores the logical clock
    and routing counters exactly, not just the store bytes."""
    path = str(tmp_path / "journal")
    stream = make_stream()
    rar, _ = run_journaled(stream, path, snapshot_every=3,
                           breaker_threshold=2)
    rar.close_shadow()                        # checkpoint frame
    holder = {}
    rar2 = RAR(FakeTier(known={0, 1}, name="weak"),
               FakeTier(known=range(10_000), can_guide=True,
                        name="strong"),
               lambda p: holder["emb"], lambda e, k: False,
               make_cfg(journal_path=path, snapshot_every=3,
                        breaker_threshold=2))
    assert rar2.now == rar.now
    assert rar2.guides_from_memory == rar.guides_from_memory
    assert rar2.guides_generated == rar.guides_generated
    assert rar2.probes_deferred == rar.probes_deferred
    assert rar2.strong.breaker.state == rar.strong.breaker.state
    # the clock resumes, it does not restart: the next request gets a
    # fresh stamp strictly after every recovered insertion
    holder["emb"] = skill_emb(stream[0][0])
    rar2.process(prompt(stream[0][0], 5), greq(stream[0][0]), key=None)
    assert rar2.now == rar.now + 1


def test_fabric_with_journal_recovers_across_restart(tmp_path):
    """The batched/replicated path journals through the shared commit
    stream: kill a fabric mid-run, rebuild on the same path, and the
    recovered store carries every committed epoch."""
    path = str(tmp_path / "journal")
    fab = build_fabric(2, weak_known={0, 1}, journal_path=path,
                       snapshot_every=2)
    serve_serialized(fab, make_stream(), 4)
    fab.close_shadow()
    ref_state = fab.memory
    ref_epoch = fab.commit_stream.buffer.epoch
    fab2 = build_fabric(2, weak_known={0, 1}, journal_path=path,
                        snapshot_every=2)
    assert_states_equal(fab2.memory, ref_state)
    assert fab2.commit_stream.buffer.epoch == ref_epoch
    out = fab2.process_batch([prompt(0, 7)], [greq(0)],
                             embs=skill_emb(0)[None])[0]
    assert out.strong_calls == 0                  # recovered store serves
    fab2.close_shadow()


# ---------------------------------------------------------------------------
# Soak: random crash/recover schedule (smoke-sized; CI runs it seeded)
# ---------------------------------------------------------------------------


def test_soak_random_fault_schedule():
    """A seeded random schedule of replica crashes + strong-tier errors
    over a threaded 3-replica fabric: every request resolves exactly
    once, the store stays consistent, and all faults fire."""
    plan = random_plan(int(os.environ.get("REPRO_SOAK_SEED", "0")),
                       replicas=3, crashes=3, tier_errors=2, horizon=30)
    fab = build_fabric(3, weak_known={0, 1}, fault_plan=plan,
                       tier_max_retries=1, breaker_threshold=2,
                       breaker_cooldown=0.05)
    rng = np.random.default_rng(0)
    tickets, n = [], 0
    for _ in range(40):
        B = int(rng.integers(1, 4))
        chunk = [(int(rng.integers(0, 10)), int(rng.integers(0, 8)))
                 for _ in range(B)]
        n += B
        tickets.append(fab.submit(
            [prompt(s, x) for s, x in chunk],
            [greq(s) for s, _ in chunk],
            embs=np.stack([skill_emb(s) for s, _ in chunk])))
    time.sleep(0.08)
    fab.flush_shadow(timeout=120)
    outs = [o for t in tickets for o in t.wait(timeout=60)]
    assert len(outs) == n                          # nothing lost/duplicated
    assert all(o.response is not None for o in outs)
    assert fab.restarts == fab.deaths              # every death restarted
    assert fab.commit_stream.buffer.entries_applied == \
        int(np.asarray(fab.memory.ptr))
    stats = fab.stats()
    assert stats["items_enqueued"] == stats["items_drained"]
    assert plan.n_fired > 0
    fab.close_shadow()
