"""Serving launcher — run the RAR layered system over a request stream.

This is the paper's deployment shape: a weak tier + strong tier behind the
adaptive router, serving batched requests. It trains (or loads from
``<checkout>/.cache/rar_system``) the synthetic-suite system — the
``rar-weak``/``rar-strong`` tiers and the embedder of
``repro.configs.rar_system`` — and serves it end to end, on the CPU or on
one accelerator. The tiers are fixed; the zoo architectures' distributed
serve step is compiled by ``repro.launch.dryrun``, not served here.

Recovery plane: the launcher exposes the fault-tolerance stack of
``repro.serving`` — tier-call retries with exponential backoff
(--tier-max-retries/--tier-timeout), a strong-tier circuit breaker that
degrades routing to weak-only while open (--breaker-threshold/
--breaker-cooldown; suppressed shadow probes are deferred and replayed
when the breaker closes; --breaker-adaptive derives the effective knobs
from an EWMA of observed error rates), bounded crash redispatch across
serve replicas (--max-redispatch), process-per-replica serving with
heartbeat-lease supervision (--transport process: a hung or SIGKILL'd
worker is detected, respawned, and its in-flight work redispatched
byte-identically), and a crash-consistent guide store via write-ahead
journaling + snapshots (--journal-path/--snapshot-every: restart with the
same path and the pre-crash memory — plus the engine-state manifest:
clock, counters, breaker state — is recovered byte-identically). All
default OFF; with the defaults the serve path is byte-identical to the
pre-resilience launcher.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --requests 200 --domain 0
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.cache import enable_compile_cache
from repro.configs.rar_system import make_rar_config
from repro.experiments.setup import build_system, failing_pool
from repro.experiments.stages import run_rar_experiment


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description="Serve a request stream through the RAR layered "
                    "system (weak/strong tiers + adaptive router + "
                    "guide memory), with optional replication and a "
                    "recovery plane: tier retries, circuit-breaker "
                    "degraded routing, crash redispatch, and "
                    "journaled crash-consistent memory.")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--domain", type=int, default=0)
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--microbatch", type=int, default=1,
                    help="requests per controller step (1 = the paper's "
                         "sequential stream; >1 = batched data plane)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve replicas behind the request dispatcher "
                         "(serving fabric): microbatches round-robin "
                         "across replica worker threads sharing one "
                         "commit stream, a single learn replica drains "
                         "all shadow work. 1 = the single-controller "
                         "data plane (bit-identical through the fabric)")
    ap.add_argument("--transport", default="thread",
                    choices=["thread", "process"],
                    help="how serve replicas are hosted (--replicas > 1 "
                         "only): 'thread' = worker threads in this "
                         "process; 'process' = one OS process per "
                         "replica behind the same submit/join boundary "
                         "— a crashed or SIGKILL'd worker is detected "
                         "by heartbeat leases, respawned, and its in-"
                         "flight microbatches redispatch byte-"
                         "identically (requires --router oracle; CPU "
                         "backend only, since a chip belongs to one "
                         "process)")
    ap.add_argument("--router", default="oracle",
                    choices=["oracle", "learned"])
    ap.add_argument("--arrival-pattern", default="closed",
                    choices=["closed", "poisson", "bursty"],
                    help="traffic shape: 'closed' (default) offers pre-"
                         "partitioned microbatches back-to-back; "
                         "'poisson'/'bursty' switch to open-loop "
                         "admission (--replicas > 1): each stage's "
                         "requests become a seeded arrival trace (one "
                         "stream per replica) admitted one by one "
                         "through the continuous batcher, which forms "
                         "microbatches with the size-or-deadline close "
                         "rule and reports queueing-delay / end-to-end "
                         "p50/p99 per stream in the metrics registry")
    ap.add_argument("--arrival-rate", type=float, default=64.0,
                    help="aggregate offered load in requests/second for "
                         "open-loop --arrival-pattern (virtual time: "
                         "the rate shapes batch formation and queueing "
                         "delay, not wall-clock pacing)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request queueing-delay budget in ms for "
                         "open-loop admission: a forming batch closes "
                         "early when its oldest member's budget is "
                         "about to breach (priority p tightens the "
                         "budget to slo/(1+p)); default: size-only "
                         "closes")
    ap.add_argument("--priorities", default=None,
                    help="comma-separated per-stream priorities for "
                         "open-loop admission, cycled across streams "
                         "(e.g. '0,1,2'); higher priority = tighter "
                         "SLO budget. Default: all zero")
    ap.add_argument("--sim-threshold", type=float, default=0.2)
    ap.add_argument("--retrieval-k", type=int, default=1,
                    help="memory entries retrieved per query (one store "
                         "pass regardless of k); >1 enables multi-guide "
                         "serving")
    ap.add_argument("--max-guides", type=int, default=None,
                    help="retrieved guides spliced into the weak FM's "
                         "prompt (default: --retrieval-k)")
    ap.add_argument("--retrieval-clusters", type=int, default=0,
                    help="two-level (IVF) retrieval plane: cluster the "
                         "memory into this many online-k-means centroids "
                         "and scan only the probed clusters' rows per "
                         "query (sub-linear in capacity). 0 (default) = "
                         "the exact full scan")
    ap.add_argument("--retrieval-probes", type=int, default=4,
                    help="clusters probed per query when "
                         "--retrieval-clusters is on: the recall-vs-"
                         "latency knob (probing all clusters reproduces "
                         "the exact scan)")
    ap.add_argument("--shadow-mode", default="inline",
                    choices=["inline", "deferred", "async", "adaptive"],
                    help="where shadow inference (weak probes, guide "
                         "generation, memory commits) runs relative to "
                         "the serve sweep: 'inline' = inside every "
                         "controller step (the reference behaviour); "
                         "'deferred' = queued and drained synchronously "
                         "every --shadow-flush-every batches; 'async' = "
                         "drained by a background thread so user-facing "
                         "latency pays for the serve sweep alone; "
                         "'adaptive' = a cost model fitted online from "
                         "drain-cost observations drains exactly when "
                         "estimated staleness cost (pending re-shadow "
                         "probability x probe cost) exceeds the "
                         "amortized drain overhead — with replicas, one "
                         "shared policy sees every replica's staleness. "
                         "Requires --microbatch > 1.")
    ap.add_argument("--shadow-flush-every", type=int, default=1,
                    help="drain the shadow queue every N batches "
                         "(deferred/async modes; 0 = only at stage-end "
                         "barriers). Larger values amortize drains at "
                         "the cost of memory staleness: a request cannot "
                         "hit a skill whose shadow pass has not drained "
                         "yet. In adaptive mode this is a hard staleness "
                         "cap on top of the cost model (0 = uncapped)")
    ap.add_argument("--shadow-dedup-sim", type=float, default=None,
                    help="coalesce queued shadow items whose embedding "
                         "cosine reaches this threshold: one probe pass "
                         "resolves the whole near-duplicate group, "
                         "reclaiming duplicate-skill strong calls "
                         "(pays off with deferred/async drains, where "
                         "duplicates pile up between barriers; default "
                         "off)")
    # -- recovery plane (all default off; off = byte-identical serve) --
    ap.add_argument("--tier-max-retries", type=int, default=0,
                    help="retries per FM tier call on transient failure "
                         "(exponential backoff + jitter); 0 = off — a "
                         "tier exception propagates as before")
    ap.add_argument("--tier-timeout", type=float, default=None,
                    help="per-call tier timeout in seconds (counts as a "
                         "transient failure toward retries/breaker); "
                         "default: no timeout")
    ap.add_argument("--breaker-threshold", type=int, default=0,
                    help="consecutive tier failures that open the "
                         "circuit breaker; while the STRONG breaker is "
                         "open, routing degrades to weak-only (memory-"
                         "hard served weak, shadow probes deferred and "
                         "replayed once a half-open probe closes the "
                         "breaker). 0 = no breaker")
    ap.add_argument("--breaker-cooldown", type=float, default=1.0,
                    help="seconds an open breaker waits before the "
                         "half-open probe call")
    ap.add_argument("--breaker-adaptive", action="store_true",
                    help="derive the breaker's effective threshold/"
                         "cooldown from an EWMA of observed tier error "
                         "rates: a tier seen to be flaky opens after "
                         "fewer consecutive failures and cools down "
                         "longer; a clean history keeps the configured "
                         "knobs exactly (default: static knobs)")
    ap.add_argument("--breaker-ewma-alpha", type=float, default=0.2,
                    help="error-rate EWMA smoothing factor in (0, 1] "
                         "for --breaker-adaptive (higher = reacts "
                         "faster, forgets faster)")
    ap.add_argument("--max-redispatch", type=int, default=2,
                    help="times a crashed replica's microbatch is re-"
                         "dispatched to a surviving replica before its "
                         "ticket surfaces the error (fabric mode; the "
                         "crash point precedes all side effects, so a "
                         "redispatched run is byte-identical)")
    ap.add_argument("--journal-path", default=None,
                    help="directory for the guide store's write-ahead "
                         "log + snapshots; every commit epoch is "
                         "journaled before it applies, and a restart "
                         "with the same path recovers the pre-crash "
                         "store byte-identically (default: no journal)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="snapshot the journaled store every N commit "
                         "epochs (bounds WAL replay length at recovery)")
    ap.add_argument("--log-every", type=int, default=64,
                    help="serve-loop progress every N requests (0 = off); "
                         "throttled because the memory-occupancy read "
                         "syncs a device scalar — per-request logging "
                         "would stall the pipeline on every request")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print a one-line metrics summary (commit "
                         "epoch, queue depth, shadow staleness, drain "
                         "counts) every N served requests (0 = off). "
                         "Reads the controller's host-side metrics "
                         "snapshot — zero device syncs")
    ap.add_argument("--metrics-json", default=None,
                    help="write the final metrics snapshot "
                         "(per-replica queue depth / shadow staleness / "
                         "drain cost / commit lag, engine + breaker "
                         "counters, supervision events, drain-policy "
                         "cost model, raw registry) to this JSON file")
    ap.add_argument("--metrics-prom", default=None,
                    help="write the final metrics-registry snapshot in "
                         "Prometheus/OpenMetrics text exposition format "
                         "to this file (counters/gauges plus summary "
                         "quantiles for every histogram — scrape-ready)")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    system = build_system()
    pool = failing_pool(system, args.domain, n=args.requests)
    print(f"[serve] {len(pool)} requests (weak-FM-failing pool, "
          f"domain {args.domain}); router={args.router}, "
          f"retrieval_k={args.retrieval_k}, shadow={args.shadow_mode}, "
          f"replicas={args.replicas}")

    if args.shadow_mode != "inline" and args.microbatch <= 1:
        ap.error("--shadow-mode deferred/async requires --microbatch > 1 "
                 "(the sequential reference interleaves shadow inference "
                 "per request)")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.transport == "process":
        if args.replicas <= 1:
            ap.error("--transport process requires --replicas > 1")
        if args.router != "oracle":
            ap.error("--transport process requires --router oracle (the "
                     "learned router is not shipped to worker processes)")
    priorities = None
    if args.arrival_pattern != "closed":
        if args.replicas <= 1:
            ap.error("--arrival-pattern poisson/bursty admits through "
                     "the serving fabric; use --replicas > 1")
        if args.arrival_rate <= 0:
            ap.error("--arrival-rate must be positive")
        if args.slo_ms is not None and args.slo_ms <= 0:
            ap.error("--slo-ms must be positive")
        if args.priorities:
            try:
                priorities = [int(p) for p in args.priorities.split(",")]
            except ValueError:
                ap.error(f"--priorities must be comma-separated ints, "
                         f"got {args.priorities!r}")
    elif args.priorities or args.slo_ms is not None:
        ap.error("--priorities/--slo-ms only apply to open-loop "
                 "--arrival-pattern poisson/bursty")
    cfg = make_rar_config(sim_threshold=args.sim_threshold,
                          retrieval_k=args.retrieval_k,
                          max_guides=args.max_guides,
                          retrieval_clusters=args.retrieval_clusters,
                          retrieval_probes=args.retrieval_probes,
                          shadow_mode=args.shadow_mode,
                          shadow_flush_every=args.shadow_flush_every,
                          shadow_dedup_sim=args.shadow_dedup_sim,
                          reprobe_period=2 * len(pool),
                          tier_max_retries=args.tier_max_retries,
                          tier_timeout=args.tier_timeout,
                          breaker_threshold=args.breaker_threshold,
                          breaker_cooldown=args.breaker_cooldown,
                          breaker_adaptive=args.breaker_adaptive,
                          breaker_ewma_alpha=args.breaker_ewma_alpha,
                          max_redispatch=args.max_redispatch,
                          journal_path=args.journal_path,
                          snapshot_every=args.snapshot_every)
    # perf_counter, not time.time(): wall-clock steps (NTP slew, DST)
    # must not corrupt the reported interval
    t0 = time.perf_counter()
    results, rar = run_rar_experiment(
        system, pool, n_stages=args.stages, rar_cfg=cfg,
        router_kind=args.router, microbatch=args.microbatch,
        replicas=args.replicas, transport=args.transport,
        arrival_pattern=args.arrival_pattern,
        arrival_rate=args.arrival_rate, slo_ms=args.slo_ms,
        priorities=priorities, verbose=True,
        progress_every=args.log_every,
        metrics_every=args.metrics_every)
    rar.close_shadow()
    # snapshot AFTER the final flush so drain counters are complete and
    # nothing is pending; metrics() stays valid on a closed fabric (all
    # counters are plain host-side state)
    final_metrics = rar.metrics() if hasattr(rar, "metrics") else None
    dt = time.perf_counter() - t0

    total = args.stages * len(pool)
    aligned = sum(r.aligned for r in results)
    strong = sum(r.strong_calls for r in results)
    print(f"[serve] {total} requests in {dt:.1f}s "
          f"({1e3 * dt / total:.1f} ms/request)")
    print(f"[serve] aligned {aligned}/{total} ({100 * aligned / total:.1f}%)"
          f", strong-FM calls {strong} ({100 * strong / total:.1f}% of "
          f"requests), memory size {rar.memory.size_fast}")
    if args.metrics_json and final_metrics is not None:
        with open(args.metrics_json, "w") as f:
            json.dump(final_metrics, f, indent=1, default=str)
        print(f"[serve] metrics snapshot -> {args.metrics_json}")
    if args.metrics_prom:
        registry = getattr(rar, "metrics_registry", None)
        if registry is None:
            registry = getattr(getattr(rar, "shadow", None),
                               "metrics", None)
        if registry is not None:
            with open(args.metrics_prom, "w") as f:
                f.write(registry.to_openmetrics())
            print(f"[serve] OpenMetrics exposition -> {args.metrics_prom}")
        else:
            print("[serve] --metrics-prom skipped: controller exposes "
                  "no metrics registry")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump([r.__dict__ for r in results], f, indent=1,
                      default=str)


if __name__ == "__main__":
    main()
