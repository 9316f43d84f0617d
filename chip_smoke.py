#!/usr/bin/env python3
"""Smoke run of the RAR system on a TPU: the quickest proof that it starts
on the chip and gives right answers there.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # the row-sharded guide store, 4 chips

One process drives the chip; nothing here starts a child that touches JAX
(a chip belongs to one process). Each phase prints one line. A failed
phase exits non-zero before the final line, which is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One chip, in order:

* device     — platform, kind and count as JAX reports them; the kernel
               dispatch must resolve to ``pallas``.
* serve      — the RAR serve path through the functions ``launch/serve.py``
               calls: train the system here (no checkpoint is loaded),
               then serve 64 domain-0 requests over 2 stages at microbatch
               8, with 1 replica and with 2 thread replicas.
* tiers      — the weak and strong tiers' prefill logits on the chip
               against the same parameters run in float32 on the host CPU.
* olmo-1b    — one zoo model at full width (bf16, seeded random weights)
               through ``ServingEngine.generate``.
* retrieval  — a 2**21 x 384 guide store (3 GiB) on the chip, top-4 for 32
               queries through the normal dispatch, against a float32
               numpy top-k on the host.

``--four-chip`` runs only the sharded store: the parity self-test on the
four devices, and a 2**22-row store sharded four ways against the same
contents in a single-device store on device 0.

Timings printed here are smoke timings of a cold process, not metrics.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
SEED = 0

print = functools.partial(print, flush=True)  # noqa: A001

# bf16 keeps 8 significant bits: unit roundoff 2**-8. A float32 matmul at
# the TPU's default precision rounds both operands to bf16 once, so each
# product is off by at most 2 * 2**-8 of its magnitude, and a dot product
# by at most 2**-7 of the sum of its terms' magnitudes.
BF16_DOT_REL = 2.0 ** -7


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


class CompileClock:
    """Seconds JAX spent compiling (or loading a compiled program from
    the persistent cache), and persistent-cache hits, in this process."""

    # JAX's monitoring events: the backend compile (a persistent-cache
    # load included) and a persistent-cache hit
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE:
                self.seconds += secs

        def on_event(name, **_):
            if name == self.CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(jax, want: int) -> dict:
    from repro.kernels import ops
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"JAX found no TPU (platform {dev['platform']!r})")
    check(dev["count"] >= want, f"{want} chips needed, {dev['count']} found")
    env = os.environ.get("REPRO_KERNEL_IMPL")
    check(env in (None, "", "pallas"),
          f"REPRO_KERNEL_IMPL={env!r}: the chip serves the Pallas kernels")
    impl = ops.default_impl()
    check(impl == "pallas", f"kernel dispatch resolved to {impl!r}")
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} kernels={impl}")
    return dev


def phase_serve(jax, clock: CompileClock):
    from repro.configs.rar_system import make_rar_config
    from repro.experiments.setup import build_system, failing_pool
    from repro.experiments.stages import run_rar_experiment

    t0, c0 = time.perf_counter(), clock.seconds
    system = build_system(cache=False, verbose=False)
    train_s = time.perf_counter() - t0
    pool = failing_pool(system, 0, n=64)
    check(len(pool) == 64, f"failing pool of {len(pool)}, not 64")
    stages, microbatch = 2, 8
    for replicas in (1, 2):
        cfg = make_rar_config(sim_threshold=0.2, reprobe_period=2 * len(pool))
        t = time.perf_counter()
        results, rar = run_rar_experiment(
            system, pool, n_stages=stages, rar_cfg=cfg, router_kind="oracle",
            microbatch=microbatch, replicas=replicas, transport="thread")
        rar.close_shadow()
        dt = time.perf_counter() - t
        total = stages * len(pool)
        resolved = sum(n for r in results for case, n in r.cases.items()
                       if case != "shadow_pending")
        strong = sum(r.strong_calls for r in results)
        size = rar.memory.size_fast
        check(resolved == total, f"{resolved}/{total} requests resolved")
        check(strong > 0, "no strong-tier call")
        check(size > 0, "empty guide memory")
        per_stage = ", ".join(f"stage {i}: aligned {r.aligned}/{r.n} "
                              f"strong {r.strong_calls}"
                              for i, r in enumerate(results))
        print(f"[serve] replicas={replicas} microbatch={microbatch} "
              f"resolved {resolved}/{total}; {per_stage}; memory {size}; "
              f"cold {1e3 * dt / total:.1f} ms/request (smoke timing, "
              f"not a metric)")
    print(f"[serve] trained on the chip in {train_s:.1f}s; phase compile "
          f"{clock.seconds - c0:.1f}s")
    return system, pool


def phase_tiers(jax, system, pool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.models import prefill

    vocab = system.suite.vocab
    tokens = np.stack([np.asarray(vocab.question(d, s, x), np.int32)
                       for d, s, x in pool[:8]])
    cpu = jax.devices("cpu")[0]
    for tier in (system.weak, system.strong):
        cfg, params = tier.cfg, tier.engine.params
        check(cfg.param_dtype == "float32", f"{cfg.name} is not float32")
        run = jax.jit(lambda p, t, cfg=cfg: prefill(
            cfg, p, {"tokens": t}, t.shape[1])[0])
        chip = np.asarray(run(params, jnp.asarray(tokens)))
        # committed to the host CPU, the same jitted prefill runs there
        ref = np.asarray(run(jax.device_put(params, cpu),
                             jax.device_put(tokens, cpu)))
        check(ref.shape == chip.shape == (8, cfg.vocab_size),
              f"{cfg.name} logits {chip.shape} vs {ref.shape}")
        check(bool(np.isfinite(chip).all()), f"{cfg.name}: non-finite")
        # each serial matmul stage adds an error of up to BF16_DOT_REL of
        # the row's logit scale: per layer the q and k projections (which
        # multiply in the scores), the scores, the value mix, the output
        # projection, the gated up projection and the down projection;
        # then the unembedding. Roundings of different stages are
        # independent, so their errors add in quadrature
        n_matmul = 7 * cfg.num_layers + 1
        scale = np.maximum(1.0, np.abs(ref).max(axis=1))
        tol = np.sqrt(n_matmul) * BF16_DOT_REL * scale
        err = np.abs(chip - ref).max(axis=1)
        check(bool((err <= tol).all()),
              f"{cfg.name}: max |chip - cpu| {err.max():.4g} > tol "
              f"{tol.min():.4g}")
        top2 = np.sort(ref, axis=1)[:, -2:]
        decided = top2[:, 1] - top2[:, 0] > tol
        agree = chip.argmax(1) == ref.argmax(1)
        check(bool(agree[decided].all()),
              f"{cfg.name}: argmax differs where the margin exceeds tol")
        print(f"[tiers] {cfg.name}: max |chip - cpu f32| {err.max():.3e} "
              f"(tol {tol.min():.3e}..{tol.max():.3e}, {n_matmul} bf16 "
              f"matmul stages), argmax agrees on {int(decided.sum())}/8 "
              f"decided rows ({int(agree.sum())}/8 overall)")


def phase_olmo(jax, clock: CompileClock, arch: str = "olmo-1b",
               B: int = 8, Lp: int = 128, new: int = 16) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import init_params, prefill
    from repro.serving.engine import ServingEngine

    cfg = configs.get(arch)
    check(cfg.param_dtype == "bfloat16", f"{arch} params are not bf16")
    key_p, key_t = jax.random.split(jax.random.PRNGKey(SEED))
    params = jax.jit(init_params, static_argnums=0)(cfg, key_p)
    tokens = jax.random.randint(key_t, (B, Lp), 0, cfg.vocab_size,
                                jnp.int32)
    engine = ServingEngine(cfg, params)
    c0, t0 = clock.seconds, time.perf_counter()
    first = np.asarray(engine.generate({"tokens": tokens}, new))
    first_s, compile_s = time.perf_counter() - t0, clock.seconds - c0
    second = np.asarray(engine.generate({"tokens": tokens}, new))
    logits = np.asarray(jax.jit(lambda p, t: prefill(
        cfg, p, {"tokens": t}, Lp + new)[0])(params, tokens), np.float32)
    check(first.shape == (B, new), f"tokens {first.shape}")
    check(bool(((first >= 0) & (first < cfg.vocab_size)).all()),
          "token ids out of the vocabulary")
    check(np.array_equal(first, second), "two calls gave different tokens")
    check(logits.shape == (B, cfg.vocab_size), f"logits {logits.shape}")
    check(bool(np.isfinite(logits).all()), "non-finite logits")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"[{arch}] {cfg.param_count() / 1e9:.2f}B params bf16, B={B} "
          f"prompt {Lp} max_new {new}: finite, repeatable; first call "
          f"{first_s:.1f}s (compile {compile_s:.1f}s); peak_bytes_in_use "
          f"{peak}")


def _seeded_store(jax, capacity: int, E: int, G: int, key, sharding=None):
    """Store contents made on the device from a seed: unit rows, about
    half of them carrying a guide, every slot valid."""
    import jax.numpy as jnp

    from repro.kernels.memory_topk import MASK_GUIDE, MASK_VALID

    def make(key):
        ke, kg, kb, kh = jax.random.split(key, 4)
        emb = jax.random.normal(ke, (capacity, E), jnp.float32)
        emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
        guide_bit = jax.random.bernoulli(kb, 0.5, (capacity, 1))
        mask = MASK_VALID + MASK_GUIDE * guide_bit.astype(jnp.int32)
        guide = jax.random.randint(kg, (capacity, G), 0, 1000, jnp.int32)
        hard = jax.random.bernoulli(kh, 0.3, (capacity,))
        added_at = jnp.arange(capacity, dtype=jnp.int32)
        return emb, mask, guide, hard, added_at

    if sharding is None:
        return jax.jit(make)(key)
    rows, repl = sharding
    return jax.jit(make, out_shardings=(rows, rows, repl, repl,
                                        repl))(key)


def _queries(jax, emb, key, B: int):
    """B unit queries: half near stored rows (a clear best row), half
    random."""
    import jax.numpy as jnp
    kr, kn, kq = jax.random.split(key, 3)
    rows = jax.random.randint(kr, (B // 2,), 0, emb.shape[0])
    near = emb[rows] + 0.05 * jax.random.normal(kn, (B // 2, emb.shape[1]))
    far = jax.random.normal(kq, (B - B // 2, emb.shape[1]))
    qs = jnp.concatenate([near, far]).astype(jnp.float32)
    return qs / jnp.linalg.norm(qs, axis=1, keepdims=True)


def _host_topk(sims, k: int):
    """Per column of (C, B) sims: the k best rows by (sim desc, row asc)
    and their sims, as (B, k) arrays."""
    import numpy as np
    part = np.argpartition(-sims, k - 1, axis=0)[:k].T          # (B, k)
    vals = np.take_along_axis(sims.T, part, axis=1)
    order = np.lexsort((part, -vals), axis=1)
    rows = np.take_along_axis(part, order, axis=1)
    return rows, np.take_along_axis(vals, order, axis=1)


def phase_retrieval(jax, C: int = 2 ** 21, E: int = 384) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import memory as mem
    from repro.kernels.memory_topk import MASK_GUIDE

    G, B, k = 8, 32, 4
    k_store, k_q = jax.random.split(jax.random.PRNGKey(SEED + 1))
    emb, mask, guide, hard, added_at = _seeded_store(jax, C, E, G, k_store)
    state = mem.MemoryState(emb=emb, mask=mask, guide=guide, hard=hard,
                            added_at=added_at,
                            ptr=jnp.asarray(C, jnp.int32))
    check(state.emb.shape == (mem.padded_rows(C), mem.padded_lanes(E)),
          f"store layout {state.emb.shape}")
    qs = _queries(jax, emb, k_q, B)
    host = {name: np.asarray(a) for name, a in jax.device_get(
        {"emb": emb, "mask": mask, "guide": guide, "hard": hard,
         "added_at": added_at, "qs": qs}).items()}
    sims_all = host["emb"] @ host["qs"].T                      # (C, B) f32
    # the kernel's dot products of unit vectors: |error| <= 2**-7
    tol = BF16_DOT_REL
    worst = 0.0
    for guides_only in (False, True):
        t0 = time.perf_counter()
        got = mem.query_topk_batch(state, qs, k,
                                   guides_only=guides_only).device_get()
        dt = time.perf_counter() - t0
        view = (host["mask"][:, 0] & mem.required_bits(guides_only)) == \
            mem.required_bits(guides_only)
        sims = np.where(view[:, None], sims_all, -2.0)
        want, want_s = _host_topk(sims, k)
        rows = got.index
        check(bool((rows >= 0).all() and (rows < C).all()),
              "row out of range")
        check(bool(view[rows].all()), "row outside the query's view")
        got_host_s = np.take_along_axis(sims.T, rows, axis=1)
        # a row may differ from the host's only where the host's own
        # scores of the two rows are within tol of each other
        check(bool((np.abs(got_host_s - want_s) <= tol).all()),
              "kernel rows are not the host's top-k")
        check(bool((np.abs(got.sim - want_s) <= tol).all()),
              "kernel sims off the host's by more than tol")
        if not guides_only:       # a planted row may carry no guide
            check(bool((rows[:B // 2, 0] == want[:B // 2, 0]).all()),
                  "a query near a stored row missed that row")
        check(np.array_equal(got.guide, host["guide"][rows]), "guides")
        check(np.array_equal(got.hard, host["hard"][rows]), "hard flags")
        check(np.array_equal(got.added_at, host["added_at"][rows]),
              "added_at")
        check(np.array_equal(got.has_guide,
                             (host["mask"][rows, 0] & MASK_GUIDE) != 0),
              "has_guide")
        err = float(np.abs(got.sim - want_s).max())
        worst = max(worst, err)
        print(f"[retrieval] C={C} E={E} B={B} k={k} "
              f"guides_only={guides_only}: rows match the host top-{k} "
              f"({int((rows == want).sum())}/{B * k} identical, rest "
              f"within tol); max |sim - host f32| {err:.3e} (tol "
              f"{tol:.3e}); first call {dt:.2f}s")
    del state, emb


def phase_four_chip(jax, C: int = 2 ** 22, E: int = 384) -> None:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import memory as mem
    from repro.core.memory_sharded import (AXIS, ShardedMemory,
                                           parity_selftest, sims_agree)

    t0 = time.perf_counter()
    report = parity_selftest()
    check(report["shards"] == 4, f"self-test on {report['shards']} shards")
    print(f"[four-chip] parity_selftest on 4 chips: {report['checks']} "
          f"checks, top-k {report['topk_checked']}, rows and meta "
          f"identical, sims within {report['sim_max_ulp']} ulp of 1.0 "
          f"({time.perf_counter() - t0:.1f}s)")

    G, B, k = 8, 32, 4
    cfg = mem.MemoryConfig(capacity=C, embed_dim=E, guide_len=G)
    sharded = ShardedMemory(cfg)
    check(sharded.shards == 4 and sharded.csp == sharded.cs,
          f"{sharded.shards} shards of {sharded.csp} padded rows")
    rows_s = NamedSharding(sharded.mesh, P(AXIS, None))
    repl = NamedSharding(sharded.mesh, P())
    k_store, k_q = jax.random.split(jax.random.PRNGKey(SEED + 2))
    (sharded.emb, sharded.mask, sharded.guide, sharded.hard,
     sharded.added_at) = _seeded_store(jax, C, E, G, k_store,
                                       (rows_s, repl))
    sharded.ptr = jnp.asarray(C, jnp.int32)
    dev0 = jax.devices()[0]
    # one shard per chip holds its rows at global row = logical slot
    # (csp == cs), so the same arrays gathered onto device 0 are the
    # single-device store's padded layout
    single = mem.MemoryState(
        emb=jax.device_put(sharded.emb, dev0),
        mask=jax.device_put(sharded.mask, dev0),
        guide=jax.device_put(sharded.guide, dev0),
        hard=jax.device_put(sharded.hard, dev0),
        added_at=jax.device_put(sharded.added_at, dev0),
        ptr=jax.device_put(sharded.ptr, dev0))
    qs = _queries(jax, single.emb, k_q, B)              # on device 0
    qs_all = jax.device_put(qs, repl)                    # on every chip
    for guides_only in (False, True):
        a = mem.query_topk_batch(single, qs, k,
                                 guides_only=guides_only).device_get()
        b = sharded.query_topk_batch(qs_all, k,
                                     guides_only=guides_only).device_get()
        check(np.array_equal(a.meta, b.meta),
              "sharded rows/meta differ from the single-device store")
        check(sims_agree(a.sim, b.sim), "sharded sims differ beyond tol")
        a1 = mem.query_topk(single, qs[0], k,
                            guides_only=guides_only).device_get()
        b1 = sharded.query_topk(qs_all[0], k,
                                guides_only=guides_only).device_get()
        check(np.array_equal(a1.meta, b1.meta) and sims_agree(a1.sim, b1.sim),
              "single-query sharded read differs")
        d = float(np.abs(a.sim.astype(np.float64) - b.sim).max())
        print(f"[four-chip] C={C} E={E} sharded 4 ways vs one store on "
              f"device 0, B={B} k={k} guides_only={guides_only}: rows and "
              f"meta identical, max |sim diff| {d:.3e} "
              f"({d / float(np.spacing(np.float32(1))):.2f} ulp of 1.0)")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the row-sharded guide store across "
                         "four chips, and what it is compared with")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    clock = CompileClock(jax)
    t_start = time.perf_counter()
    phase = "device"
    try:
        dev = phase_device(jax, 4 if args.four_chip else 1)
        if args.four_chip:
            phase = "four-chip"
            phase_four_chip(jax)
        else:
            phase = "serve"
            system, pool = phase_serve(jax, clock)
            phase = "tiers"
            phase_tiers(jax, system, pool)
            del system
            phase = "olmo-1b"
            phase_olmo(jax, clock)
            phase = "retrieval"
            phase_retrieval(jax)
    except Exception as e:  # noqa: BLE001 — report the phase, then exit 1
        traceback.print_exc()
        print(f"chip_smoke: phase {phase} failed: {e}", file=sys.stderr)
        return 1
    entries = sum(1 for _ in Path(cache_dir).glob("*")) \
        if Path(cache_dir).is_dir() else 0
    print(f"[compile] {clock.seconds:.1f}s compiling or loading programs, "
          f"{clock.cache_hits} persistent-cache hits; cache {cache_dir} "
          f"({entries} entries); whole run "
          f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
