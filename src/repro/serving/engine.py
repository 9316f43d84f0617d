"""Batched serving: prefill + greedy decode over the unified model API.

``ServingEngine`` maintains a jit cache keyed on (batch, prompt_len,
max_new) so repeated calls with uniform-shaped request batches (the common
case in the RAR evaluation loop: unguided / guided / guide-request prompts
each have a fixed length) hit compiled code.

``generate_bucketed`` extends this to mixed-length request groups (the
microbatched RAR controller mixes guided and unguided prompts in one
sweep): prompts are grouped by exact length — a causal LM cannot be
length-padded without shifting positions — and each group's batch dim is
padded up to a power-of-two bucket, so arbitrary traffic compiles at most
O(#lengths · log max_batch) variants instead of one per observed shape.

This is the same ``prefill`` / ``decode_step`` pair the multi-pod dry-run
lowers at production shapes — the engine is the single-host driver of it.
"""
from __future__ import annotations

import threading
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, prefill
from repro.models.config import ModelConfig
from repro.serving.metrics import count_syncs, span


def bucket_batch(n: int) -> int:
    """Smallest power of two ≥ n — the batch-dim bucket sizes."""
    b = 1
    while b < n:
        b *= 2
    return b


def greedy_generate(cfg: ModelConfig, params: Any, batch: dict,
                    max_new: int) -> jax.Array:
    """Greedy decode ``max_new`` tokens after the prompt.

    batch["tokens"]: (B, Lp) un-padded prompts (uniform length).
    Returns (B, max_new) int32.
    """
    tokens = batch["tokens"]
    B, Lp = tokens.shape
    extra = cfg.num_patches if cfg.family == "vlm" else 0
    max_len = Lp + extra + max_new
    logits, cache, pos = prefill(cfg, params, batch, max_len)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def body(carry, _):
        tok, cache, pos = carry
        logits, cache = decode_step(cfg, params, tok, cache, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, cache, pos + 1), tok

    (_, _, _), outs = jax.lax.scan(body, (tok, cache, pos),
                                   None, length=max_new)
    return jnp.moveaxis(outs, 0, 1)  # (B, max_new)


class ServingEngine:
    """Jit-cached greedy serving for one model."""

    def __init__(self, cfg: ModelConfig, params: Any):
        self.cfg = cfg
        self.params = params
        self._jitted: dict[tuple, Any] = {}
        self.calls = 0          # inference calls served (RAR cost metric)
        self.tokens_processed = 0
        self.jit_hits = 0       # generate() reused a compiled variant
        self.jit_misses = 0     # generate() traced + compiled a new one
        # registry for the ``host/syncs/engine`` counter (the fabric sets
        # its own; None counts on the thread's tally alone)
        self.metrics = None
        # the async shadow drainer serves sweeps on its own thread while
        # the serve plane keeps generating — the jit-cache dict and the
        # cost counters (non-atomic read-modify-writes) need a lock to
        # stay exact under that concurrency
        self._lock = threading.Lock()

    def _bill(self, calls: int, tokens: int) -> None:
        with self._lock:
            self.calls += calls
            self.tokens_processed += tokens

    def generate(self, batch: dict, max_new: int) -> jax.Array:
        tokens = batch["tokens"]
        key = (tokens.shape, max_new) + tuple(sorted(
            k for k in batch if k != "tokens"))
        with self._lock:
            fn = self._jitted.get(key)
            if fn is None:
                self.jit_misses += 1
                fn = self._jitted[key] = jax.jit(
                    partial(greedy_generate, self.cfg, max_new=max_new))
            else:
                self.jit_hits += 1
        out = fn(params=self.params, batch=batch)
        self._bill(tokens.shape[0], tokens.size + out.size)
        return out

    def generate_bucketed(self, prompts: Sequence[np.ndarray],
                          max_new: int) -> np.ndarray:
        """Serve a mixed-length prompt list in one sweep. Prompts are
        grouped by exact length; each group is padded along batch to the
        power-of-two bucket (dummy rows replicate the group's first
        prompt, their outputs are dropped and they are not billed as
        calls). ``calls`` stays logical (real requests only) while
        ``tokens_processed``/``flops_spent`` stay physical — padding rows
        do consume compute and are deliberately included there.
        Each group is one ``rar.engine.launch`` span (stack, transfer,
        dispatch) and one ``rar.engine.fetch`` span (the blocking wait for
        its tokens, a ``host/syncs/engine`` count).
        Returns (N, max_new) int32 in input order."""
        by_len: dict[int, list[int]] = {}
        for i, p in enumerate(prompts):
            by_len.setdefault(len(p), []).append(i)
        out = np.zeros((len(prompts), max_new), np.int32)
        for L, idxs in sorted(by_len.items()):
            B = len(idxs)
            Bp = bucket_batch(B)
            with span("rar.engine.launch"):
                batch = np.stack([np.asarray(prompts[i], np.int32)
                                  for i in idxs] +
                                 [np.asarray(prompts[idxs[0]], np.int32)] *
                                 (Bp - B))
                pending = self.generate({"tokens": jnp.asarray(batch)},
                                        max_new)
            with span("rar.engine.fetch"):
                got = np.asarray(pending)
            count_syncs(self.metrics, "engine")
            self._bill(-(Bp - B), 0)      # padding rows are not requests
            out[idxs] = got[:B]
        return out

    @property
    def flops_spent(self) -> float:
        """Forward-pass FLOPs of every token processed: 2 N per token
        (N active parameters; serving runs no backward pass, so not the
        training count ``cfg.flops_per_token()`` = 6 N)."""
        return self.tokens_processed * 2.0 * self.cfg.active_param_count()

    def stats(self) -> dict:
        """Consistent host-side counter snapshot (one lock hold, no
        device syncs) — per-tier rows for the fabric's ``stats()`` and
        the throughput bench."""
        with self._lock:
            return {"calls": self.calls,
                    "tokens_processed": self.tokens_processed,
                    "flops_spent": self.flops_spent,
                    "jit_variants": len(self._jitted),
                    "jit_hits": self.jit_hits,
                    "jit_misses": self.jit_misses}

    # -- crash-recovery manifest hooks ----------------------------------
    def export_counters(self) -> dict:
        """The cost-accounting state (not the jit cache — compiled
        functions are rebuilt on demand) for the recovery manifest."""
        with self._lock:
            return {"calls": self.calls,
                    "tokens_processed": self.tokens_processed}

    def restore_counters(self, st: dict) -> None:
        with self._lock:
            self.calls = st["calls"]
            self.tokens_processed = st["tokens_processed"]
