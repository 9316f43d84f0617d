"""Sharded skill/guide memory — the (C, E) ring spread across devices.

Scales the store past one device's HBM (the ROADMAP's "sharded memory"
item): logical ring slots [0, C) are row-sharded over a 1-D ``"mem"`` mesh
axis, shard *s* owning slots [s·Cs, (s+1)·Cs) with Cs = C/S. Each shard
keeps its slice in the same persistent padded kernel layout as the
single-device :class:`repro.core.memory.MemoryState` — (Csp, Ep) f32
embeddings plus the (Csp, 1) int32 valid/has_guide mask bit plane — so the
read path per shard is the *identical* zero-copy Pallas kernel
(:mod:`repro.kernels.memory_topk` via ``shard_map``), streaming only the
local shard once per query.

Combine: each shard produces its local (best sim, best row, mask bits);
an all-gather of those S-scalar triples plus an argmax over the shard axis
yields the global (sim, index). ``argmax`` takes the first maximum, so
cross-shard ties resolve to the lowest shard — which, with the in-kernel
lowest-row tie-break, makes the result **bit-identical** to the
single-device kernel (same f32 row dot products, same lowest-global-row
tie-break; asserted in ``tests/test_memory_sharded.py``). At S scalars per
query the gather is equivalent to a psum-tree combine and simpler.

Top-k (:meth:`ShardedMemory.query_topk` / :meth:`query_topk_batch`): each
shard computes its local top-k with the same zero-copy kernel, the S·k
(sim, global row, mask bits) candidate triples are all-gathered and
re-selected by the shared (sim desc, row asc) extraction rule
(:func:`_merge_topk` — the same total order as the kernel accumulator and
the ref oracle), so the global top-k is bit-identical to single-device,
ties included. k is capped at Cs rows so a shard's candidates can never
include local padding rows, whose global slot numbers would collide with
the next shard's.

Writes: FIFO ring-pointer arithmetic maps a global slot g to
(shard g // Cs, row g mod Cs). A microbatch commit broadcasts the K padded
rows + mask bits with their global slots; every shard turns the slots into
local rows, clamps out-of-range ones to the (out-of-bounds) padding row
and scatters with ``mode="drop"`` — one scatter per shard regardless of
how the batch straddles shard boundaries. Per-entry metadata that never
feeds the kernel (guide tokens, hard flags, timestamps — O(C·G) int32,
bytes next to the O(C·E) f32 store) stays replicated so the query epilogue
and flag updates (:meth:`mark_soft`/:meth:`touch`) remain single cheap
scatters.

The controller-facing API mirrors :mod:`repro.core.memory`:
:meth:`ShardedMemory.query` / :meth:`query_batch` return the same packed
:class:`~repro.core.memory.QueryResult`, and
:meth:`add` / :meth:`add_batch` / :meth:`mark_soft` / :meth:`touch` keep
microbatch-commit semantics, so ``MicrobatchRAR`` can serve against either
store.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import memory as mem
from repro.kernels import ops as kops
from repro.kernels.memory_topk import (MASK_VALID, _select_topk,
                                       padded_lanes, padded_rows)

AXIS = "mem"

# How far a sharded sim may sit from the single-device one: the same f32
# row dot product, computed in matrix products of different shapes, so
# summed in another order. The products' magnitudes of two unit vectors
# add up to at most 1, so the rounding difference is counted in ulps of
# 1.0 (2**-23), not of the result, which may be near zero.
SIM_MAX_ULP = 4


def sims_agree(a, b) -> bool:
    """True where two stores' sims for the same rows agree to within
    :data:`SIM_MAX_ULP` ulps of 1.0 (exact for the -2.0/-3.0
    sentinels, which are never computed)."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return bool(np.all(d <= SIM_MAX_ULP * np.spacing(np.float32(1))))


def make_memory_mesh(shards: int | None = None,
                     devices: list | None = None) -> Mesh:
    """1-D mesh over the devices carrying the store."""
    devices = devices if devices is not None else jax.devices()
    shards = shards or len(devices)
    # Auto axes: the store's eager scatters (commits, flag updates,
    # ``to_single_device``) rely on propagated shardings, which Explicit
    # axes (``make_mesh``'s default) reject
    return jax.make_mesh((shards,), (AXIS,), axis_types=(AxisType.Auto,),
                         devices=devices[:shards])


# ---------------------------------------------------------------------------
# Jitted collectives (mesh/geometry static, shapes traced)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh", "cs", "required"))
def _query_sharded(mesh: Mesh, cs: int, required: int,
                   emb: jax.Array, mask: jax.Array, q: jax.Array
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single query → replicated (sim (), global logical idx (), bits ())."""

    def local(emb_s, mask_s, q):
        sim, idx = kops.memory_top1_padded(emb_s, q, mask_s, required)
        bits = mask_s[idx, 0]
        sims = jax.lax.all_gather(sim, AXIS)          # (S,)
        idxs = jax.lax.all_gather(idx, AXIS)
        bitss = jax.lax.all_gather(bits, AXIS)
        s = jnp.argmax(sims)            # first max → lowest shard on ties
        return sims[s], s.astype(jnp.int32) * cs + idxs[s], bitss[s]

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS, None), P()),
                         out_specs=(P(), P(), P()), check_vma=False
                         )(emb, mask, q)


@partial(jax.jit, static_argnames=("mesh", "cs", "required"))
def _query_batch_sharded(mesh: Mesh, cs: int, required: int,
                         emb: jax.Array, mask: jax.Array, qs: jax.Array
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched queries → replicated (sims (B,), idx (B,), bits (B,))."""

    def local(emb_s, mask_s, qs):
        sim, idx = kops.memory_top1_batch_padded(emb_s, qs, mask_s, required)
        bits = mask_s[idx, 0]
        sims = jax.lax.all_gather(sim, AXIS)          # (S, B)
        idxs = jax.lax.all_gather(idx, AXIS)
        bitss = jax.lax.all_gather(bits, AXIS)
        s = jnp.argmax(sims, axis=0)                  # (B,)
        take = lambda a: jnp.take_along_axis(a, s[None], axis=0)[0]  # noqa: E731
        return take(sims), s.astype(jnp.int32) * cs + take(idxs), take(bitss)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS, None), P()),
                         out_specs=(P(), P(), P()), check_vma=False
                         )(emb, mask, qs)


def _merge_topk(sims: jax.Array, rows: jax.Array, bits: jax.Array, k: int
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Global top-k of the (S·k, …) per-shard candidates via the
    kernel's own selection rule (:func:`…memory_topk._select_topk` —
    sim desc, global row asc), so the combined result is bit-identical
    to single-device, ties included. Global rows are unique across
    candidates (shards own disjoint slot ranges and k ≤ Cs keeps local
    padding rows out of the per-shard top-k), so the winners' mask bits
    recover through a one-hot row-match sum."""
    out_s, out_r = _select_topk(sims, rows, k)
    hit = rows[None] == out_r[:, None]             # (k, S·k, …) one-hot
    out_b = jnp.sum(jnp.where(hit, bits[None], 0), axis=1)
    return out_s, out_r, out_b


@partial(jax.jit, static_argnames=("mesh", "cs", "k", "required"))
def _query_topk_sharded(mesh: Mesh, cs: int, k: int, required: int,
                        emb: jax.Array, mask: jax.Array, q: jax.Array
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single query → replicated (sims (k,), global idx (k,), bits (k,))."""

    def local(emb_s, mask_s, q):
        sims, idx = kops.memory_topk_padded(emb_s, q, mask_s, k, required)
        bits = mask_s[idx, 0]
        s = jax.lax.axis_index(AXIS)
        S = jax.lax.psum(1, AXIS)
        cand_s = jax.lax.all_gather(sims, AXIS).reshape(S * k)
        cand_r = jax.lax.all_gather(s.astype(jnp.int32) * cs + idx,
                                    AXIS).reshape(S * k)
        cand_b = jax.lax.all_gather(bits, AXIS).reshape(S * k)
        return _merge_topk(cand_s, cand_r, cand_b, k)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS, None), P()),
                         out_specs=(P(), P(), P()), check_vma=False
                         )(emb, mask, q)


@partial(jax.jit, static_argnames=("mesh", "cs", "k", "required"))
def _query_topk_batch_sharded(mesh: Mesh, cs: int, k: int, required: int,
                              emb: jax.Array, mask: jax.Array,
                              qs: jax.Array
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched queries → replicated ((B, k) sims, idx, bits)."""

    def local(emb_s, mask_s, qs):
        sims, idx = kops.memory_topk_batch_padded(emb_s, qs, mask_s, k,
                                                  required)      # (B, k)
        bits = mask_s[idx, 0]
        s = jax.lax.axis_index(AXIS)
        S = jax.lax.psum(1, AXIS)
        B = qs.shape[0]
        gather = lambda a: jax.lax.all_gather(                 # noqa: E731
            a.T, AXIS).reshape(S * k, B)                       # (S·k, B)
        out_s, out_r, out_b = _merge_topk(
            gather(sims), gather(s.astype(jnp.int32) * cs + idx),
            gather(bits), k)                                   # (k, B)
        return out_s.T, out_r.T, out_b.T

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS, None), P()),
                         out_specs=(P(), P(), P()), check_vma=False
                         )(emb, mask, qs)


@partial(jax.jit, static_argnames=("mesh", "cs", "csp"))
def _commit_sharded(mesh: Mesh, cs: int, csp: int,
                    emb: jax.Array, mask: jax.Array,
                    rows_p: jax.Array, bits: jax.Array, slots: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Scatter K padded rows + mask bits at global logical ``slots`` —
    exactly one scatter per shard (out-of-shard entries clamp to the
    padding row and drop)."""

    def local(emb_s, mask_s, rows_p, bits, slots):
        s = jax.lax.axis_index(AXIS)
        loc = slots - s * cs
        in_range = (loc >= 0) & (loc < cs)
        rows = jnp.where(in_range, loc, csp)          # csp = OOB → dropped
        return (emb_s.at[rows].set(rows_p, mode="drop"),
                mask_s.at[rows, 0].set(bits, mode="drop"))

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS, None), P(AXIS, None),
                                   P(), P(), P()),
                         out_specs=(P(AXIS, None), P(AXIS, None)),
                         check_vma=False)(emb, mask, rows_p, bits, slots)


@jax.jit
def _commit_meta(guide, hard, added_at, slots, guides, hards, nows):
    """The replicated-metadata half of a commit as one fused dispatch
    (mirrors the single-device ``_add_batch_jit``)."""
    return (guide.at[slots].set(guides),
            hard.at[slots].set(hards),
            added_at.at[slots].set(nows))


# ---------------------------------------------------------------------------
# The store
# ---------------------------------------------------------------------------


class ShardedMemory:
    """Row-sharded ring store with the single-device query/commit API."""

    def __init__(self, cfg: mem.MemoryConfig, mesh: Mesh | None = None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_memory_mesh()
        self.shards = self.mesh.shape[AXIS]
        if cfg.capacity % self.shards:
            raise ValueError(f"capacity {cfg.capacity} not divisible by "
                             f"{self.shards} shards")
        self.cs = cfg.capacity // self.shards         # logical rows/shard
        self.csp = padded_rows(self.cs)               # padded rows/shard
        self.ep = padded_lanes(cfg.embed_dim)
        row_sharded = NamedSharding(self.mesh, P(AXIS, None))
        repl = NamedSharding(self.mesh, P())
        S, C, G = self.shards, cfg.capacity, cfg.guide_len
        self.emb = jax.device_put(
            jnp.zeros((S * self.csp, self.ep), jnp.float32), row_sharded)
        self.mask = jax.device_put(
            jnp.zeros((S * self.csp, 1), jnp.int32), row_sharded)
        self.guide = jax.device_put(jnp.zeros((C, G), jnp.int32), repl)
        self.hard = jax.device_put(jnp.zeros((C,), bool), repl)
        self.added_at = jax.device_put(jnp.zeros((C,), jnp.int32), repl)
        self.ptr = jnp.zeros((), jnp.int32)

    # -- occupancy ------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.cfg.capacity

    @property
    def size_fast(self) -> int:
        return min(int(self.ptr), self.capacity)

    # -- reads ----------------------------------------------------------
    def query(self, emb: jax.Array,
              guides_only: bool = False) -> mem.QueryResult:
        sim, idx, bits = _query_sharded(self.mesh, self.cs,
                                        mem.required_bits(guides_only),
                                        self.emb, self.mask,
                                        jnp.asarray(emb))
        return mem.QueryResult(
            sim=sim, meta=mem.pack_meta_jit(idx, bits, self.hard,
                                            self.added_at, self.guide))

    def query_batch(self, embs: jax.Array,
                    guides_only: bool = False) -> mem.QueryResult:
        sims, idx, bits = _query_batch_sharded(self.mesh, self.cs,
                                               mem.required_bits(guides_only),
                                               self.emb, self.mask,
                                               jnp.asarray(embs))
        return mem.QueryResult(
            sim=sims, meta=mem.pack_meta_jit(idx, bits, self.hard,
                                             self.added_at, self.guide))

    def _check_topk(self, k: int) -> None:
        mem._check_k(k, self.capacity)
        if k > self.cs:
            # each shard must supply k real (non-padding) local rows so
            # the global merge never sees a local padding row, whose
            # global slot number would collide with the next shard's
            raise ValueError(f"retrieval k={k} exceeds the {self.cs} "
                             f"logical rows per shard ({self.shards} "
                             f"shards over capacity {self.capacity})")

    def query_topk(self, emb: jax.Array, k: int,
                   guides_only: bool = False) -> mem.TopKResult:
        self._check_topk(k)
        sims, idx, bits = _query_topk_sharded(
            self.mesh, self.cs, k, mem.required_bits(guides_only),
            self.emb, self.mask, jnp.asarray(emb))
        return mem.TopKResult(
            sim=sims, meta=mem.pack_meta_jit(idx, bits, self.hard,
                                             self.added_at, self.guide))

    def query_topk_batch(self, embs: jax.Array, k: int,
                         guides_only: bool = False) -> mem.TopKResult:
        self._check_topk(k)
        sims, idx, bits = _query_topk_batch_sharded(
            self.mesh, self.cs, k, mem.required_bits(guides_only),
            self.emb, self.mask, jnp.asarray(embs))
        return mem.TopKResult(
            sim=sims, meta=mem.pack_meta_jit(idx, bits, self.hard,
                                             self.added_at, self.guide))

    # -- writes ---------------------------------------------------------
    def add(self, emb: jax.Array, guide: jax.Array, has_guide, hard,
            now) -> None:
        self.add_batch(jnp.asarray(emb)[None], jnp.asarray(guide)[None],
                       jnp.asarray([has_guide]), jnp.asarray([hard]),
                       jnp.asarray([now], jnp.int32))

    def add_batch(self, embs: jax.Array, guides: jax.Array,
                  has_guide: jax.Array, hard: jax.Array,
                  now: jax.Array) -> None:
        """Microbatch commit at consecutive ring slots (FIFO), identical
        semantics to :func:`repro.core.memory.add_batch`."""
        K, C = embs.shape[0], self.capacity
        if K > C:
            raise ValueError(f"microbatch commit of {K} entries exceeds "
                             f"memory capacity {C}")
        slots = (self.ptr + jnp.arange(K, dtype=jnp.int32)) % C
        # same encoding helpers as MemoryState — the bit layout must never
        # diverge between the two stores
        rows_p = mem._pad_lanes(jnp.asarray(embs), self.ep)
        bits = mem._mask_bits(jnp.asarray(has_guide))
        self.emb, self.mask = _commit_sharded(
            self.mesh, self.cs, self.csp, self.emb, self.mask,
            rows_p, bits, slots)
        self.guide, self.hard, self.added_at = _commit_meta(
            self.guide, self.hard, self.added_at, slots,
            jnp.asarray(guides), jnp.asarray(hard), jnp.asarray(now))
        self.ptr = self.ptr + K

    def mark_soft(self, index: jax.Array) -> None:
        self.hard = self.hard.at[index].set(False)

    def touch(self, index: jax.Array, now: jax.Array) -> None:
        self.added_at = self.added_at.at[index].set(now)

    # -- debug / parity -------------------------------------------------
    def debug_size(self) -> int:
        """Debugging-only occupancy — a blocking cross-shard reduction
        (device sync); a method, not a property, so the sync is loud at
        call sites. Hot paths use :attr:`size_fast` / host counters (see
        :meth:`repro.core.memory.MemoryState.debug_size`)."""
        return int(jnp.sum((jnp.asarray(self.mask)[:, 0] & MASK_VALID)
                           != 0))

    def to_single_device(self) -> mem.MemoryState:
        """Gather the shards back into a single-device
        :class:`~repro.core.memory.MemoryState` (tests/checkpointing)."""
        C, E = self.cfg.capacity, self.cfg.embed_dim
        S = self.shards
        emb = jnp.asarray(self.emb).reshape(S, self.csp, self.ep)
        emb = emb[:, :self.cs].reshape(C, self.ep)
        bits = jnp.asarray(self.mask).reshape(S, self.csp)
        bits = bits[:, :self.cs].reshape(C)
        state = mem.init_memory(self.cfg)
        return dataclasses.replace(
            state,
            emb=state.emb.at[:C].set(emb),
            mask=state.mask.at[:C, 0].set(bits),
            guide=jnp.asarray(self.guide),
            hard=jnp.asarray(self.hard),
            added_at=jnp.asarray(self.added_at),
            ptr=jnp.asarray(self.ptr),
        )


# ---------------------------------------------------------------------------
# Parity self-test — run as ``python -m repro.core.memory_sharded`` with
# ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to exercise a real
# multi-shard mesh on CPU (used by tests/test_memory_sharded.py and
# benchmarks/memory_bench.py via subprocess, since forcing placeholder
# devices must happen before jax initializes).
# ---------------------------------------------------------------------------


def parity_selftest(capacity: int = 64, embed_dim: int = 16,
                    guide_len: int = 4, n_commits: int = 6,
                    n_queries: int = 16, seed: int = 0) -> dict:
    """Drive a single-device MemoryState and a ShardedMemory through the
    same commit stream (wraparound, duplicate rows for tie-breaks) and
    assert identical rows — and full metadata — on every query, in both
    mask views, with sims that agree by :func:`sims_agree`: the two
    stores multiply matrices of different shapes (a (Cs, E) shard
    against the whole (C, E) ring), so a dot product may round
    differently in its last bits. Every other commit wave is routed through the
    epoch-versioned :class:`repro.core.memory.CommitBuffer` (the shadow
    queue's deferred-commit path, staged in shuffled order + flag updates
    with duplicate targets) so the buffer's sorted apply is pinned across
    both store flavours too. Returns a summary dict."""
    def same_sims(a, b):
        assert sims_agree(a, b), (a, b)

    cfg = mem.MemoryConfig(capacity=capacity, embed_dim=embed_dim,
                           guide_len=guide_len)
    rng = np.random.default_rng(seed)
    single = mem.init_memory(cfg)
    sharded = ShardedMemory(cfg)
    checks = 0
    deferred_epochs = 0
    for step in range(n_commits):
        K = int(rng.integers(1, max(2, capacity // 2)))
        embs = rng.normal(size=(K, embed_dim)).astype(np.float32)
        embs /= np.linalg.norm(embs, axis=1, keepdims=True)
        if K > 3:
            embs[2] = embs[0]          # exact duplicate → tie-break path
        guides = rng.integers(0, 50, size=(K, guide_len)).astype(np.int32)
        hg = rng.random(K) < 0.5
        hd = rng.random(K) < 0.3
        now = (np.arange(K) + step * capacity).astype(np.int32)
        args = (jnp.asarray(embs), jnp.asarray(guides), jnp.asarray(hg),
                jnp.asarray(hd), jnp.asarray(now))
        if step % 2:
            # deferred-commit sweep: stage in a shuffled order (the apply
            # must sort by logical time), plus flag updates incl. a
            # duplicate touch target (last-now-wins) — one epoch apply
            # per store, then the usual bit-identical query checks below
            order = rng.permutation(K)
            stores = [single, sharded]
            for si, store in enumerate(stores):
                buf = mem.CommitBuffer()
                for j in order:
                    buf.stage_add(embs[j], guides[j], bool(hg[j]),
                                  bool(hd[j]), int(now[j]))
                t = int(now[-1])
                buf.stage_touch(0, t + 1)
                buf.stage_touch(0, t + 2)      # duplicate → later now wins
                buf.stage_soft_clear(1, t + 1)
                stores[si], n = buf.apply(store)
                assert n == K and buf.epoch == 1 and buf.pending == 0
            single, sharded = stores
            deferred_epochs += 1
        else:
            single = mem.add_batch(single, *args)
            sharded.add_batch(*args)

        qs = rng.normal(size=(n_queries, embed_dim)).astype(np.float32)
        qs /= np.linalg.norm(qs, axis=1, keepdims=True)
        qs[0] = embs[0]                # exact stored row (duplicated above)
        topks = [k for k in (1, 2, 4, 8)
                 if k <= capacity // sharded.shards]
        for guides_only in (False, True):
            a = mem.query_batch(single, jnp.asarray(qs),
                                guides_only=guides_only).device_get()
            b = sharded.query_batch(jnp.asarray(qs),
                                    guides_only=guides_only).device_get()
            same_sims(a.sim, b.sim)
            assert np.array_equal(a.meta, b.meta), (step, a.meta, b.meta)
            a1 = mem.query(single, jnp.asarray(qs[0]),
                           guides_only=guides_only).device_get()
            b1 = sharded.query(jnp.asarray(qs[0]),
                               guides_only=guides_only).device_get()
            same_sims(a1.sim, b1.sim)
            assert np.array_equal(a1.meta, b1.meta)
            checks += 2 * n_queries + 2
            # top-k: the global merge of per-shard candidates must pick
            # the single-device kernel's rows, ties included
            for k in topks:
                ak = mem.query_topk_batch(single, jnp.asarray(qs), k,
                                          guides_only=guides_only
                                          ).device_get()
                bk = sharded.query_topk_batch(jnp.asarray(qs), k,
                                              guides_only=guides_only
                                              ).device_get()
                same_sims(ak.sim, bk.sim)
                assert np.array_equal(ak.meta, bk.meta), (step, k)
                a1k = mem.query_topk(single, jnp.asarray(qs[0]), k,
                                     guides_only=guides_only).device_get()
                b1k = sharded.query_topk(jnp.asarray(qs[0]), k,
                                         guides_only=guides_only
                                         ).device_get()
                same_sims(a1k.sim, b1k.sim)
                assert np.array_equal(a1k.meta, b1k.meta), (step, k)
                checks += 2 * n_queries * k + 2 * k
    assert sharded.size_fast == single.size_fast
    assert deferred_epochs > 0, "deferred-commit sweep never ran"
    return {"shards": sharded.shards, "capacity": capacity,
            "checks": checks, "topk_checked": topks,
            "deferred_commit_epochs": deferred_epochs,
            "rows_meta_identical": True, "sim_max_ulp": SIM_MAX_ULP}


if __name__ == "__main__":
    import json
    print(json.dumps(parity_selftest()))
