"""One run of one cell: set-up, a paced warm-up stretch, the measured
window, the comparison that decides ``correct``, and the result.

The window drives the program's serve path as a deployment would:
open-loop arrivals go into the program's admission scheduler
(``ContinuousBatcher``, size-or-deadline close, paced on the wall clock),
which submits microbatches to a one-replica thread ``ServingFabric``
whose replica runs ``MicrobatchRAR.process_batch`` with the shadow drain
inline. Requests carry text; the embedder runs as the fabric's
``embed_fn``. The store is planted full and injected as the fabric's
``memory``.

:func:`run_cell` is the Python API; ``bench/run.py`` is the command.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import time

import numpy as np

from bench import check as CK
from bench import flops as F
from bench import probes as PR
from bench import spec as S
from bench import store as ST
from bench import trace as TR
from bench import traffic as TF
from bench import weights as W

SRC = S.ROOT / "src"
WAIT_AFTER_S = 60.0          # how long a request may finish after close
TRACE_DIR = S.ROOT / ".cache" / "bench_trace"
TOPK_KERNEL = r"^%\S*topk\S*pallas\S* = .*custom-call"  # the Pallas top-k kernel
GUIDED_EXTRA = (2, 3, 4)     # tokens a spliced guide adds (PAD dropped)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _import_program():
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"no program under {SRC}: run from a "
                                f"checkout of the repository")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r})")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


def _keys(jax, seed: int):
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(4)
    return [jax.random.PRNGKey(int(w)) for w in words]


def calibrate(planted: np.ndarray, second: np.ndarray) -> float:
    """The paper's threshold rule (§IV-A2), on this seed's embedder: a
    cut between the similarities of two questions of one skill and of
    questions of different skills, halfway between the 1st percentile of
    the first and the 99th of the second."""
    n = len(second)
    same = np.sum(planted[:n] * second, axis=1)
    cross = planted[:n] @ planted[:n].T
    cross = cross[~np.eye(n, dtype=bool)]
    return float((np.quantile(same, 0.01) + np.quantile(cross, 0.99)) / 2)


@dataclasses.dataclass
class Built:
    tr: TF.Traffic
    requests: dict
    weak: PR.TierProbe
    strong: PR.TierProbe
    embed: PR.EmbedProbe
    fabric: object
    proxy: PR.FabricProxy
    state: object
    plant: object             # () -> the planted store arrays, made anew
    weights: dict
    rar_cfg: object
    mem: object


def build(cell: S.Cell, seed: int, seconds: float, jax) -> Built:
    import jax.numpy as jnp

    from repro.configs.rar_system import make_rar_config
    from repro.core import embedder as emb_mod
    from repro.core import memory as mem
    from repro.core.embedder import EmbedderConfig
    from repro.core.fm import FMTier
    from repro.data.tokenizer import Vocab
    from repro.kernels.memory_topk import padded_lanes, padded_rows
    from repro.models.config import ModelConfig
    from repro.serving.engine import ServingEngine
    from repro.serving.fabric import ServingFabric

    from bench import reference as R

    cfg, mix = cell.config, cell.mix
    st = cfg["store"]
    C, E, G = st["capacity"], st["embed_dim"], st["guide_len"]
    tr = TF.generate(mix, float(cell.cell["rate_rps"]), seconds, seed, G)
    k_store, k_weak, k_strong, k_emb = _keys(jax, seed)

    emb_params = W.make_embedder(cfg["embedder"], k_emb)
    planted_emb = R.embed_many(cfg["embedder"], emb_params,
                               tr.planted_prompts)
    second = R.embed_many(cfg["embedder"], emb_params, tr.calib_prompts)
    thr = calibrate(planted_emb, second)
    kinds = np.asarray(TF.KINDS)[tr.known_kind]

    def plant():
        return ST.plant(st, k_store, jnp.asarray(planted_emb),
                        jnp.asarray(tr.known_guides),
                        jnp.asarray(kinds == "guide"),
                        jnp.asarray(kinds == "hard"),
                        first=int(mix["first_content_token"]),
                        vocab=int(mix["vocab"]), padded_rows=padded_rows,
                        padded_lanes=padded_lanes)

    state = mem.MemoryState(*plant(), ptr=jnp.asarray(C, jnp.int32))
    weights = {"embedder": emb_params,
               "weak": W.make_tier(cfg["weak"], k_weak),
               "strong": W.make_tier(cfg["strong"], k_strong)}
    tap = PR.tap_engine_class(ServingEngine)
    tiers = {}
    for name in ("weak", "strong"):
        mcfg = ModelConfig(**cfg[name])
        tiers[name] = PR.TierProbe(
            FMTier(name=name, cfg=mcfg, engine=tap(mcfg, weights[name]),
                   vocab=Vocab()), name)
    ecfg = EmbedderConfig(**cfg["embedder"])
    embed_jit = jax.jit(lambda p, t: emb_mod.embed(ecfg, p, t)[0])
    tail = int(mix["prompt_len"]) - int(mix["skill_part_len"])
    embed = PR.EmbedProbe(
        lambda prompt: embed_jit(emb_params, jnp.asarray(prompt)[None]), tail)
    rar_cfg = make_rar_config(
        sim_threshold=thr,
        memory=mem.MemoryConfig(capacity=C, embed_dim=E, guide_len=G))
    fabric = ServingFabric(tiers["weak"], tiers["strong"], embed,
                           lambda e, k: False, rar_cfg, replicas=1,
                           memory=state)
    requests = {r.rid: r for r in tr.warmup + tr.window}
    return Built(tr=tr, requests=requests, weak=tiers["weak"],
                 strong=tiers["strong"], embed=embed, fabric=fabric,
                 proxy=PR.FabricProxy(fabric), state=state, plant=plant,
                 weights=weights, rar_cfg=rar_cfg, mem=mem)


def warm_shapes(b: Built, cell: S.Cell) -> None:
    """Compile (or load from the cache) every program the cell's traffic
    can run, through the same objects the window uses."""
    import jax.numpy as jnp
    mix, mem = cell.mix, b.mem
    L, Lg = int(mix["prompt_len"]), int(mix["guide_request_len"])
    E, G = cell.config["store"]["embed_dim"], cell.config["store"]["guide_len"]
    mb = int(mix["microbatch"])
    buckets = sorted({1 << i for i in range(mb.bit_length())
                      if (1 << i) <= mb} | {1 << (mb - 1).bit_length()})
    for B in buckets:
        for n in (L,) + tuple(L + x for x in GUIDED_EXTRA):
            b.weak.inner.engine.generate(
                {"tokens": jnp.ones((B, n), jnp.int32)}, 1)
        b.strong.inner.engine.generate(
            {"tokens": jnp.ones((B, L), jnp.int32)}, 1)
        b.strong.inner.engine.generate(
            {"tokens": jnp.ones((B, Lg), jnp.int32)}, 2).block_until_ready()
    b.embed.fn(np.ones((L,), np.int32)).block_until_ready()
    for B in range(1, mb + 1):
        for guides_only in (False, True):
            mem.query_topk_batch(b.state, jnp.zeros((B, E), jnp.float32),
                                 b.rar_cfg.retrieval_k,
                                 guides_only=guides_only).device_get()
    for K in buckets:
        idx = jnp.arange(K, dtype=jnp.int32)
        mem.add_batch(b.state, jnp.zeros((K, E), jnp.float32),
                      jnp.zeros((K, G), jnp.int32), jnp.zeros((K,), bool),
                      jnp.zeros((K,), bool), idx).ptr.block_until_ready()
        mem.mark_soft(b.state, idx).ptr.block_until_ready()
        mem.touch(b.state, idx, idx).ptr.block_until_ready()


def drive(b: Built, reqs: list, mix: dict, t0: float):
    """Offer ``reqs`` open loop: each is due at ``t0 + r.t``; microbatches
    close on size or on the close deadline at their own instants, paced
    on the wall clock. Returns the scheduler (its dispatch log)."""
    from repro.serving.scheduler import ContinuousBatcher, Request
    batcher = ContinuousBatcher(b.proxy, microbatch=int(mix["microbatch"]),
                                slo_ms=float(mix["close_ms"]), pace=True)
    # virtual time 0 of the arrivals is t0 on the wall clock (the
    # scheduler otherwise starts its clock at the first close)
    batcher._t0_wall = t0
    for r in reqs:
        batcher.admit(Request(arrival_s=r.t, stream=0, prompt=r.prompt,
                              guide_request=r.greq, key=r.rid, index=r.rid))
    batcher.advance(reqs[-1].t + float(mix["close_ms"]) / 1e3)
    batcher.flush()
    return batcher


def draw_sample(seed: int, finished: list, n: int) -> list:
    """Request ids whose tier calls the reference recomputes: drawn from
    the seed among the finished ones, with up to a quarter of them from
    the longest kind (requests that took the shadow path)."""
    rng = np.random.default_rng(np.random.SeedSequence(
        int(seed) % (1 << 64)).generate_state(2).tolist() + [7])
    longest = [w["rid"] for w in finished
               if w["outcome"] is not None
               and not w["outcome"].case.startswith("memory_")]
    first = rng.permutation(longest)[:n // 4].tolist()
    rest = [w["rid"] for w in finished if w["rid"] not in set(first)]
    more = rng.permutation(rest)[:max(0, n - len(first))].tolist()
    return sorted(first + more)


def _pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else float("nan")


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads (``metrics/<name>.py``)."""
    config: dict
    seconds: float
    window: list            # dicts: due, submitted, resolved (monotonic)
    calls: list             # probes.Call inside the window
    embeds: list            # (start, seconds) inside the window
    drain_s: list           # drain seconds inside the window
    trace: object           # trace.Summary, or None untraced
    peak: dict              # bench/peaks.json entry of the device
    prompt_len: int         # tokens of every request's prompt
    flops: object = F


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             cell: S.Cell | None = None, require_tpu: bool = True,
             hook=None, control: bool = False, log=print) -> dict:
    """One run; returns the result dict of the printed line. ``cell``
    overrides what ``BENCHMARK.json`` names (tests run tiny cells on the
    CPU with ``require_tpu=False``); ``hook(built)`` may break the timed
    path underneath, for the tests that see ``correct`` fail."""
    t_start = time.monotonic()
    _import_program()
    from repro.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    # every program goes into the cache, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cell = cell or S.load_cell(workload)
    dev = device_info(jax, cell.chips, require_tpu)
    peak = S.peaks(dev["kind"]) if require_tpu else None
    clock = PR.CompileClock()
    t_build = time.monotonic()
    b = build(cell, seed, seconds, jax)
    if hook is not None:
        hook(b)
    t_shapes = time.monotonic()
    warm_shapes(b, cell)
    b.state = None        # the fabric holds the store from here on
    mix = cell.mix
    t_stretch = time.monotonic()

    # the warm-up stretch: the same mix, paced, served to the end
    for probe in (b.weak, b.strong):
        probe.log = []
    t0 = time.monotonic() + 0.05
    drive(b, b.tr.warmup, mix, t0)
    b.proxy.wait_all(time.monotonic() + WAIT_AFTER_S)
    b.embed.log = []
    drains = b.fabric.metrics_registry.histogram(
        "replica0/shadow/drain_seconds")
    drains_before = len(drains._samples)
    compiles_before = (clock.count, clock.seconds)
    n_warm_batches = len(b.proxy.batches)

    if trace:
        if TRACE_DIR.exists():
            shutil.rmtree(TRACE_DIR)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t0 = time.monotonic() + 0.05
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        t_span = time.monotonic()
        batcher = drive(b, b.tr.window, mix, t0)
        t_end = t0 + seconds
        time.sleep(max(0.0, t_end - time.monotonic()))
    if trace:
        jax.profiler.stop_trace()
    all_done = b.proxy.wait_all(t_end + WAIT_AFTER_S)
    compiles = (clock.count - compiles_before[0],
                clock.seconds - compiles_before[1])

    # what happened to each request of the window
    rec = {}
    window_batches = b.proxy.batches[n_warm_batches:]
    for bt in window_batches:
        for i, rid in enumerate(bt.rids):
            ok = bt.outcomes is not None and i < len(bt.outcomes)
            rec[rid] = (bt.submitted, bt.resolved if ok else None,
                        bt.outcomes[i] if ok else None)
    window = []
    for r in b.tr.window:
        sub, res, out = rec.get(r.rid, (None, None, None))
        window.append({"rid": r.rid, "due": t0 + r.t, "submitted": sub,
                       "resolved": res, "outcome": out})
    finished = [w for w in window if w["resolved"] is not None]
    failed = len(window) - len(finished)
    horizon = t_end + WAIT_AFTER_S
    lat = [((w["resolved"] if w["resolved"] is not None else horizon)
            - w["due"]) * 1e3 for w in window]
    in_window = sum(1 for w in finished if w["resolved"] <= t_end)
    strong_calls = sum(len(p) for c in b.strong.log if c.start >= t0
                       for p in [c.prompts])
    lateness = [(bt.submitted - (t0 + d.dispatch_s)) * 1e3
                for bt, d in zip(b.proxy.batches[n_warm_batches:],
                                 batcher.dispatches)]
    log(f"[load] {len(window)} requests in {seconds:g}s, "
        f"{len(batcher.dispatches)} microbatches, closes "
        f"{batcher.closes}; generator late_ms p50 {_pct(lateness, 50):.3f} "
        f"p99 {_pct(lateness, 99):.3f} max {max(lateness or [0]):.3f}")
    thirds = [_pct(lat[i * len(lat) // 3:(i + 1) * len(lat) // 3], 50)
              for i in range(3)]
    log(f"[latency] p50 by thirds of the window (a backlog that grows "
        f"through it shows as a rising row): "
        f"{' '.join(f'{x:.1f}' for x in thirds)} ms; {failed} of "
        f"{len(window)} unfinished {WAIT_AFTER_S:g}s after the close")
    log(f"[setup] {setup_s:.1f}s: runtime start {t_build - t_start:.1f}s, "
        f"traffic, reference embeds, store and weights "
        f"{t_shapes - t_build:.1f}s, every shape {t_stretch - t_shapes:.1f}s, "
        f"warm-up stretch {t0 - t_stretch:.1f}s")
    log(f"[compile] in window: {compiles[0]} programs, {compiles[1]:.3f}s; "
        f"whole run {clock.count} programs, {clock.seconds:.1f}s, "
        f"{clock.cache_hits} persistent-cache hits; cache {cache_dir}")
    e2e = {
        "req_per_s": (in_window / seconds, "req/s"),
        "latency_p50_ms": (_pct(lat, 50), "ms"),
        "latency_p95_ms": (_pct(lat, 95), "ms"),
        "strong_share": (strong_calls / max(1, len(window)), "calls/req"),
        "setup_s": (setup_s, "s"),
    }
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem_peak}

    # per-layer inputs, before the program's state goes
    ctx = Context(
        config=cell.config, seconds=seconds, window=window,
        calls=[c for c in b.weak.log + b.strong.log
               if t0 <= c.start <= t_end],
        embeds=[e for e in b.embed.log if t0 <= e[0] <= t_end],
        drain_s=list(drains._samples[drains_before:]),
        trace=None, peak=peak, prompt_len=int(mix["prompt_len"]))

    # the program's store after the run, then free the program's state
    memory = b.fabric.learn.memory
    n_commit = int(memory.ptr) - cell.config["store"]["capacity"]
    E = cell.config["store"]["embed_dim"]
    n_rows = max(0, min(n_commit, cell.config["store"]["capacity"]))
    store_after = {
        "ptr": int(memory.ptr),
        "emb": np.asarray(memory.emb[:n_rows, :E]),
        "has_guide": np.asarray((memory.mask[:n_rows, 0] & 2) != 0),
        "hard": np.asarray(memory.hard[:n_rows]),
        "guide": np.asarray(memory.guide[:n_rows]),
        "added_at": np.asarray(memory.added_at[:n_rows])}
    order = [bt.rids for bt in b.proxy.batches]
    outcomes = {}
    for bt in b.proxy.batches:
        if bt.outcomes is not None:
            for rid, o in zip(bt.rids, bt.outcomes):
                outcomes[rid] = (o.served_by, o.strong_calls, o.case)
    calls = b.weak.log + b.strong.log
    del batcher
    b.proxy.close()
    if all_done:
        b.fabric.close()
    del memory
    b.fabric = b.proxy = None
    gc.collect()

    sample = draw_sample(seed, finished, int(cell.config["check_requests"]))
    inp = CK.Inputs(
        config=cell.config, requests=b.requests, batches=order,
        outcomes=outcomes, calls=calls, prog_emb=b.embed.embs,
        sim_threshold=b.rar_cfg.sim_threshold,
        guide_threshold=b.rar_cfg.guide_sim_threshold,
        reprobe_period=b.rar_cfg.reprobe_period, planted=b.plant(),
        store_after=store_after, weights=b.weights, sample=sample,
        tail=int(mix["prompt_len"]) - int(mix["skill_part_len"]))
    t_check = time.monotonic()
    readings = CK.run(inp, control=control)
    limits = cell.config["limits"]
    checks = CK.program_checks(readings, limits)
    correct = failed == 0 and CK.judge(checks)
    log(f"[check] {readings['sampled']} sampled requests, "
        f"{readings['weak_tokens']} weak and {readings['strong_tokens']} "
        f"strong served tokens compared, {readings['commits']} commits, "
        f"{readings['mismatched']} replay mismatches "
        f"{readings['mismatch_examples']}; {time.monotonic() - t_check:.1f}s")

    result = {"correct": bool(correct), "attempted": len(window),
              "failed": failed}
    if trace:
        in_flight = [(bt.submitted - t_span, bt.resolved - t_span)
                     for bt in window_batches if bt.resolved is not None]
        summary = TR.reduce_file(TR.find_xplane(str(TRACE_DIR)),
                                 {"topk": TOPK_KERNEL}, in_flight)
        ctx.trace = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        metrics = {}
        for m in cell.per_layer:
            v = S.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = TR.breakdown(summary)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update({"metrics": metrics, "device": device})
    if control:
        ctl = CK.control_checks(readings, limits)
        result["control"] = {"correct": CK.judge(ctl), "checks": ctl}
    result["readings"] = readings
    result["checks"] = checks
    if TRACE_DIR.exists():
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return result


NO_MATCH = 1e300     # a compared number that has no finite reading


def print_result(result: dict, err=sys.stderr) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output. A reading with no finite value (a
    replay mismatch) prints as ``NO_MATCH``."""
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = NO_MATCH
    for k, v in result["readings"].items():
        if isinstance(v, float) and not math.isfinite(v):
            result["readings"][k] = NO_MATCH
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err,
              flush=True)
    print(json.dumps(result, default=float), flush=True)
