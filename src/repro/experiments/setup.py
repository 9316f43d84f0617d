"""Train and assemble the full RAR evaluation system.

Everything the paper's experiment needs, built with the framework's own
substrates: the weak/strong FMs (trained with ``repro.training``), the
contrastive embedder, the static routers, and the evaluation pools
("failing samples" subsets mirroring the paper's MMLU selection, Fig. 3).

Artifacts are checkpointed under ``<checkout>/.cache/rar_system/``
(:mod:`repro.cache`) so tests, benchmarks and examples of one checkout
share one trained system.
"""
from __future__ import annotations

import dataclasses
import os
import functools
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import CACHE_ROOT
from repro.configs import rar_system
from repro.core import embedder as emb
from repro.core.fm import FMTier
from repro.core.router import LearnedRouter, OracleRouter, train_router
from repro.data.tasks import TaskSuite, TaskSuiteConfig
from repro.training import (AdamWConfig, init_opt_state, load_checkpoint,
                            make_train_step, save_checkpoint)

CACHE_DIR = str(CACHE_ROOT / "rar_system")

print = functools.partial(print, flush=True)  # noqa: A001 — logs stream to files


@dataclasses.dataclass
class TrainedSystem:
    suite: TaskSuite
    weak: FMTier
    strong: FMTier
    embedder_params: Any
    router: LearnedRouter
    embed_batch_fn: Any            # (B, L) tokens -> (B, 384)

    # ------------------------------------------------------------------
    def embed_one(self, prompt: np.ndarray) -> np.ndarray:
        L = self.suite.cfg.seq_len
        padded = np.full((1, L), 0, np.int32)
        padded[0, :len(prompt)] = prompt
        return np.asarray(self.embed_batch_fn(jnp.asarray(padded))[0])

    def embed_many(self, prompts: list[np.ndarray]) -> np.ndarray:
        L = self.suite.cfg.seq_len
        padded = np.zeros((len(prompts), L), np.int32)
        for i, p in enumerate(prompts):
            padded[i, :len(p)] = p
        return np.asarray(self.embed_batch_fn(jnp.asarray(padded)))


# ---------------------------------------------------------------------------
# FM training
# ---------------------------------------------------------------------------


def _train_lm(cfg, batch_fn, steps: int, batch_size: int, seed: int,
              lr: float = 1e-3, log_every: int = 200) -> Any:
    from repro.models import init_params
    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key)
    opt_cfg = AdamWConfig(learning_rate=lr, warmup_steps=50,
                          total_steps=steps, weight_decay=0.01,
                          beta2=0.98)
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for i in range(steps):
        batch = batch_fn(rng, batch_size)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if log_every and (i + 1) % log_every == 0:
            print(f"  [{cfg.name}] step {i + 1}/{steps} "
                  f"loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f} "
                  f"({time.perf_counter() - t0:.0f}s)")
    return params


def _train_embedder(ecfg, suite: TaskSuite, steps: int, batch_pairs: int,
                    seed: int, log_every: int = 200) -> Any:
    key = jax.random.PRNGKey(seed + 7)
    params = emb.init_params(ecfg, key)
    opt = emb.init_opt(params)
    step = emb.make_train_step(ecfg)
    rng = np.random.default_rng(seed + 7)
    for i in range(steps):
        toks, sids = suite.embedder_batch(rng, batch_pairs)
        params, opt, loss = step(params, opt, jnp.asarray(toks),
                                 jnp.asarray(sids))
        if log_every and (i + 1) % log_every == 0:
            print(f"  [embedder] step {i + 1}/{steps} "
                  f"ntxent={float(loss):.4f}")
    return params


# ---------------------------------------------------------------------------
# System assembly
# ---------------------------------------------------------------------------


def build_system(suite_cfg: TaskSuiteConfig = TaskSuiteConfig(), *,
                 weak_steps: int = 900, strong_steps: int = 1100,
                 embedder_steps: int = 400, batch_size: int = 96,
                 seed: int = 0, cache: bool = True,
                 verbose: bool = True) -> TrainedSystem:
    suite = TaskSuite(suite_cfg)
    ckpt = os.path.join(
        CACHE_DIR,
        f"sys_{suite_cfg.seed}_{suite_cfg.guide_train_frac}_{weak_steps}_{strong_steps}_{seed}.npz")

    if cache and os.path.exists(ckpt):
        if verbose:
            print(f"[setup] loading cached system from {ckpt}")
        blob = jax.tree.map(jnp.asarray, load_checkpoint(ckpt))
        weak_params, strong_params = blob["weak"], blob["strong"]
        embedder_params = blob["embedder"]
        router = LearnedRouter(w=jnp.asarray(blob["router_w"]),
                               b=jnp.asarray(blob["router_b"]))
    else:
        if verbose:
            print("[setup] training weak FM "
                  f"({rar_system.WEAK.param_count():,} params)")
        log_every = 200 if verbose else 0
        weak_params = _train_lm(rar_system.WEAK, suite.weak_train_batch,
                                weak_steps, batch_size, seed,
                                log_every=log_every)
        if verbose:
            print("[setup] training strong FM "
                  f"({rar_system.STRONG.param_count():,} params)")
        strong_params = _train_lm(rar_system.STRONG, suite.strong_train_batch,
                                  strong_steps, batch_size, seed + 1,
                                  log_every=log_every)
        if verbose:
            print("[setup] training contrastive embedder")
        embedder_params = _train_embedder(rar_system.EMBEDDER, suite,
                                          embedder_steps, 48, seed,
                                          log_every=log_every)
        router = None  # built below, needs the weak FM

    embed_fn = jax.jit(partial(emb.embed, rar_system.EMBEDDER,
                               embedder_params))
    weak = FMTier.create("weak", rar_system.WEAK, weak_params, suite.vocab)
    strong = FMTier.create("strong", rar_system.STRONG, strong_params,
                           suite.vocab)

    if router is None:
        if verbose:
            print("[setup] profiling weak FM + training static router")
        router = _build_learned_router(suite, weak, embed_fn, seed)
        if cache:
            save_checkpoint(ckpt, {
                "weak": weak_params, "strong": strong_params,
                "embedder": embedder_params,
                "router_w": router.w, "router_b": router.b})
            if verbose:
                print(f"[setup] cached system at {ckpt}")

    return TrainedSystem(suite=suite, weak=weak, strong=strong,
                         embedder_params=embedder_params, router=router,
                         embed_batch_fn=embed_fn)


def _build_learned_router(suite: TaskSuite, weak: FMTier, embed_fn,
                          seed: int, n_profile: int = 600) -> LearnedRouter:
    """RouteLLM analog: profile the weak FM on held-out questions and fit
    a logistic router on (embedding → success)."""
    rng = np.random.default_rng(seed + 100)
    prompts, labels = [], []
    L = suite.cfg.seq_len
    for _ in range(n_profile):
        d = int(rng.integers(0, suite.cfg.n_domains))
        s = int(rng.choice(suite.domain_skills[d]))
        x = int(rng.integers(0, suite.cfg.max_operand))
        prompts.append(np.asarray(suite.vocab.question(d, s, x), np.int32))
        labels.append(suite.answer(s, x))
    maxlen = max(len(p) for p in prompts)
    batch = np.zeros((n_profile, maxlen), np.int32)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = p
    # uniform length in this suite → answer in one batched call
    ans = weak.answer_batch(batch)
    success = (ans == np.asarray(labels)).astype(np.float32)
    padded = np.zeros((n_profile, L), np.int32)
    padded[:, :maxlen] = batch
    embs = np.asarray(embed_fn(jnp.asarray(padded)))
    return train_router(embs, success)


# ---------------------------------------------------------------------------
# Evaluation pools — the paper's "failing samples" subsets (Fig. 3)
# ---------------------------------------------------------------------------

POOL_SIZES = {0: 754, 1: 359, 2: 675}   # prof. law / HS psych / moral scen.
POOL_NAMES = {0: "professional_law", 1: "high_school_psychology",
              2: "moral_scenarios"}


def failing_pool(system: TrainedSystem, domain: int, *,
                 n: int | None = None, seed: int = 1234
                 ) -> list[tuple[int, int, int]]:
    """Questions of one domain that the weak FM fails unaided — the
    paper's data selection (weak-FM-failed subsets of MMLU)."""
    n = n or POOL_SIZES[domain]
    suite = system.suite
    limit = len(suite.domain_skills[domain]) * suite.cfg.max_operand
    draw = min(int(n * 2.2), limit)
    while True:
        cands = suite.question_pool(domain, draw, seed)
        prompts = np.stack([
            np.asarray(suite.vocab.question(d, s, x), np.int32)
            for d, s, x in cands])
        ans = system.weak.answer_batch(prompts)
        truth = np.asarray([suite.answer(s, x) for _, s, x in cands])
        failing = [c for c, a, t in zip(cands, ans, truth) if a != t]
        if len(failing) >= n or draw == limit:
            break
        # a weak tier that fails fewer questions widens the candidate
        # pool; one seed draws the same questions first, so a pool that
        # was wide enough is unchanged
        draw = min(2 * draw, limit)
    assert len(failing) >= n, (len(failing), n)
    return failing[:n]
