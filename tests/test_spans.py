"""Spans and counters of the serve path: the ``rar.*`` spans one
microbatch opens, read back from a CPU profiler session; the
``host/syncs/*`` counts of a known partition; the replica FIFO wait that
the fabric's tickets stamp; and the span helper with no session open."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_rar_controller import FakeTier, greq, make_cfg, prompt, skill_emb

from repro import configs
from repro.models import init_params
from repro.serving import metrics as M
from repro.serving.engine import ServingEngine
from repro.serving.fabric import ServingFabric

SITES = ("embed", "lookup", "engine", "commit")


class EngineTier(FakeTier):
    """``FakeTier``'s answers, each sweep also served through a real
    ``ServingEngine`` (so it runs the engine's per-length launch and
    fetch)."""

    def __init__(self, engine, **kw):
        super().__init__(**kw)
        self.engine = engine

    def _serve(self, prompts, max_new):
        V = self.engine.cfg.vocab_size
        self.engine.generate_bucketed(
            [np.asarray(p, np.int32) % V for p in prompts], max_new)

    def answer_many(self, prompts):
        self._serve(prompts, 1)
        return self.answer_batch(prompts)

    def generate_guides_many(self, requests, guide_len):
        self._serve(requests, 2)
        return self.generate_guides(requests, guide_len)


@pytest.fixture(scope="module")
def engines():
    cfg = configs.get_smoke("olmo-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params), ServingEngine(cfg, params)


def _fabric(engines):
    for e in engines:
        e.metrics = None          # counted into this fabric's registry
    weak = EngineTier(engines[0], known={0, 1}, name="weak")
    strong = EngineTier(engines[1], known=range(10_000), can_guide=True,
                        name="strong")
    return ServingFabric(weak, strong,
                         lambda p: jnp.asarray(skill_emb(int(p[1]))),
                         lambda e, k: False, make_cfg(), replicas=1)


def _batch(skills):
    return [prompt(s, 0) for s in skills], [greq(s) for s in skills]


# the second microbatch of _fabric: skill 0 a bare hit (weak, length 3),
# skill 2 a guided hit (weak, length 6), skill 3 a miss (strong answer,
# then a drain: weak probe, a guide-only read, a fresh guide and a guided
# probe, one commit)
FIRST, SECOND = (0, 1, 2), (0, 2, 3)
SECOND_SYNCS = {"embed": 3,          # one per embed_fn call
                "lookup": 2,         # the serve read and the 2a read
                "engine": 6,         # strong 1 + weak serve 2 (lengths
                                     # 3, 6) + probe 1 + guide 1 + probe 1
                "commit": 0}         # the stream's host pointer mirror
                                     # was read at the first commit


def _spans(log_dir) -> list[tuple]:
    import glob
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for p in jax.profiler.ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("rar."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_microbatch_spans_nest_and_carry_the_ticket(engines, tmp_path):
    fab = _fabric(engines)
    fab.submit(*_batch(FIRST)).wait()
    jax.profiler.start_trace(str(tmp_path))
    try:
        ticket = fab.submit(*_batch(SECOND))
        ticket.wait()
    finally:
        jax.profiler.stop_trace()
    fab.close_shadow()
    spans = _spans(tmp_path)
    names = {s[0] for s in spans}
    assert names == {"rar.batch", "rar.embed", "rar.lookup", "rar.decide",
                     "rar.strong", "rar.weak", "rar.drain",
                     "rar.drain.weak_probe", "rar.drain.guide_probe",
                     "rar.drain.fresh_guide", "rar.commit",
                     "rar.engine.launch", "rar.engine.fetch"}
    by = {n: [s for s in spans if s[0] == n] for n in names}
    (batch,) = by["rar.batch"]
    assert batch[3]["batch"] == ticket.id == 1
    assert batch[3]["wait_us"] >= 0
    assert batch[3]["syncs"] == sum(SECOND_SYNCS.values())
    (drain,) = by["rar.drain"]
    (commit,) = by["rar.commit"]
    (strong,) = by["rar.strong"]
    assert _inside(commit, drain) and _inside(drain, batch)
    assert any(_inside(f, strong) for f in by["rar.engine.fetch"])
    for s in spans:
        assert _inside(s, batch), s[0]
    # one launch and one fetch per exact-length group of every sweep
    assert len(by["rar.engine.fetch"]) == SECOND_SYNCS["engine"]
    assert len(by["rar.engine.launch"]) == SECOND_SYNCS["engine"]
    assert len(spans) <= 40
    # decide spans never nest in one another (their seconds add up)
    for a in by["rar.decide"]:
        assert not any(a is not b and _inside(a, b)
                       for b in by["rar.decide"])


def test_host_sync_counters_match_the_partition(engines):
    fab = _fabric(engines)
    reg = fab.metrics_registry
    fab.process_batch(*_batch(FIRST))
    before = {s: reg.counter(f"host/syncs/{s}").get() for s in SITES}
    tally = M.thread_syncs()
    fab.process_batch(*_batch(SECOND))
    got = {s: reg.counter(f"host/syncs/{s}").get() - before[s]
           for s in SITES}
    assert got == SECOND_SYNCS
    assert M.thread_syncs() - tally == sum(SECOND_SYNCS.values())
    # the first microbatch: three misses, no serve-plane weak sweep
    assert before == {"embed": 3, "lookup": 2, "engine": 4, "commit": 1}
    snap = fab.metrics()["registry"]
    assert snap["commit/apply_seconds"]["count"] == 2
    fab.close_shadow()


def test_replica_wait_covers_a_busy_worker():
    hold = 0.2
    entered, release = threading.Event(), threading.Event()

    def embed(p):
        if int(p[1]) == 7:                 # the first microbatch's request
            entered.set()
            release.wait(10.0)
        return skill_emb(int(p[1]))

    fab = ServingFabric(FakeTier(known=range(100), name="weak"),
                        FakeTier(known=range(100), can_guide=True,
                                 name="strong"),
                        embed, lambda e, k: False, make_cfg(), replicas=1)
    first = fab.submit([prompt(7, 0)], [greq(7)])
    assert entered.wait(10.0)
    second = fab.submit([prompt(8, 0)], [greq(8)])
    time.sleep(hold)
    release.set()
    first.wait(10.0)
    second.wait(10.0)
    assert (first.id, second.id) == (0, 1)
    assert first.started - first.submitted < hold
    assert second.started - second.submitted >= hold
    h = fab.metrics()["registry"]["replica0/fabric/wait_seconds"]
    assert h["count"] == 2 and h["total"] >= hold
    fab.close_shadow()


def test_span_without_a_session_is_a_shared_no_op():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    a, b = M.span("rar.x", batch=3), M.span("rar.y")
    assert a is b
    with a as entered:
        entered.set_metadata(syncs=1)
    reg = M.MetricsRegistry()
    n = M.thread_syncs()
    M.count_syncs(reg, "lookup", 2)
    M.count_syncs(None, "embed")
    assert reg.snapshot() == {"host/syncs/lookup": 2}
    assert M.thread_syncs() - n == 3
    seen = []
    t = threading.Thread(target=lambda: seen.append(M.thread_syncs()))
    t.start()
    t.join()
    assert seen == [0]                 # the tally is the thread's own
