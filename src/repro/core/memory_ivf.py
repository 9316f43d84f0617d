"""IVF two-level retrieval plane — sub-linear guide-store reads.

The exact store scan (:mod:`repro.core.memory`) touches all C rows per
query; at C = 65536 that single pass caps the whole serving fabric. This
module adds the ROADMAP's hierarchical memory: an inverted-file (IVF)
index over the same store, queried in two levels:

1. **Route** — score the query against P cluster centroids (the
   :mod:`repro.kernels.memory_ivf` kernel; centroid plane kept in the
   same zero-copy padded layout as the store) and take the top-P'
   clusters under THE (score desc, row asc) total order.
2. **Scan** — gather only the probed clusters' member rows into a small
   (L, Ep) buffer, *sorted by global slot*, and run the **existing**
   zero-copy top-k kernel over it. Because the candidates are
   slot-sorted, the kernel's local lowest-row tie-break equals the
   global (sim desc, slot asc) order — the result ranking is the exact
   scan's for every entry the probed clusters cover.

The exact scan stays the **default** (``RARConfig.retrieval_clusters =
0``: controllers never construct this wrapper — byte-identity pinned in
``tests/test_memory_ivf.py``) and the **oracle**: recall@k of the IVF
path is property-measured against ``mem.query_topk`` on the same backing
store, and probing *all* clusters reproduces the oracle's valid entries
exactly.

Centroid maintenance (online k-means, incrementally on add)
-----------------------------------------------------------
The first P inserts seed clusters 0..P-1 round-robin; each later insert
is assigned to the nearest centroid (batch-start centroids within one
``add_batch`` — minibatch k-means) and updates that cluster's running
mean (``csum/ccount``), renormalized for cosine routing. Member lists
are fixed-width (P, M) slot buckets with FIFO ring eviction: a bucket
overflow drops the cluster's *oldest* member from the index (bounded
recall loss, counted in :meth:`IVFMemory.stats`); a store-ring overwrite
removes the slot from its old bucket before re-bucketing. Entries
evicted from a bucket or overwritten in the ring have ``assign[slot]``
cleared, and the query path re-checks ``assign[slot] == probed cluster``
on gather — stale member-list entries can never surface (nor duplicate
a candidate). :meth:`IVFMemory.reindex` rebuilds the whole index from
the backing store (vectorized k-means with two refinement sweeps) —
used at attach time over a populated store and after
:meth:`IVFMemory.grow`.

Index mutation runs on the learn path (commit drains — it shares the
store's write serialization: the commit stream's lock covers both) and
is host-side numpy; device mirrors refresh lazily before the next query.

Cluster → shard placement
-------------------------
Over a :class:`~repro.core.memory_sharded.ShardedMemory` backing,
cluster c lives with shard ``c % S``: the route runs per-shard over that
shard's centroid *subset* and the S partial routes merge under the same
(score desc, cluster asc) order — bit-identical to routing the global
centroid plane (the merge is :func:`repro.kernels.ref._topk_select`,
THE shared total order), pinned in the test suite. This subsumes the
per-replica memory-shard follow-up: replicas probing their local subset
and merging lose nothing vs. a global route.

Host-offload tiering (cold clusters)
------------------------------------
With ``offload=True`` a host mirror of the store rows backs **cold**
clusters (not routed to within the last ``cold_after`` queries): their
candidate rows are gathered from the mirror and uploaded with the query
while hot clusters gather on-device — modelling an HBM tier that keeps
only hot clusters resident. Costs one extra host sync per query (the
routed cluster ids come back to pick the tier); results are pinned
bit-identical to the non-offload path, and :meth:`IVFMemory.stats`
reports the host/device row traffic split.

Recall-vs-latency knob: ``probes`` (CLI ``--retrieval-probes``). Scan
work is O(P + P'·M) rows instead of O(C); raising ``probes`` toward
``clusters`` trades latency for recall, reaching exactness at the top.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memory as mem
from repro.core.memory_sharded import ShardedMemory
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.memory_topk import MASK_VALID, _round_up, padded_rows


# ---------------------------------------------------------------------------
# Jitted query path
# ---------------------------------------------------------------------------


def _route_merged(planes, q, n_probe: int):
    """Level 1 inside the jitted query: route each centroid plane (one
    per shard — a single plane when unsharded), map subset rows to global
    cluster ids, and merge the partials under the shared total order.
    Padding/sentinel subset rows map to the 2**30 sentinel id; their
    -2.0 scores drop them at the gather stage."""
    scores, cids = [], []
    for cent, cmask, cidmap in planes:
        s, c = kops.ivf_route_padded(cent, q, cmask, n_probe, MASK_VALID)
        ps = cidmap.shape[0]
        g = jnp.where(c < ps, cidmap[jnp.clip(c, 0, ps - 1)],
                      jnp.int32(2 ** 30))
        scores.append(s)
        cids.append(g)
    if len(planes) == 1:
        return scores[0], cids[0]
    return ref._topk_select(jnp.concatenate(scores),
                            jnp.concatenate(cids), n_probe)


def _route_merged_batch(planes, qs, n_probe: int):
    scores, cids = [], []
    for cent, cmask, cidmap in planes:
        s, c = kops.ivf_route_batch_padded(cent, qs, cmask, n_probe,
                                           MASK_VALID)      # (B, n_probe)
        ps = cidmap.shape[0]
        g = jnp.where(c < ps, cidmap[jnp.clip(c, 0, ps - 1)],
                      jnp.int32(2 ** 30))
        scores.append(s.T)
        cids.append(g.T)
    if len(planes) == 1:
        return scores[0].T, cids[0].T
    ms, mc = ref._topk_select(jnp.concatenate(scores, axis=0),
                              jnp.concatenate(cids, axis=0), n_probe)
    return ms.T, mc.T


def _phys_rows(slots, cs: int, csp: int):
    """Logical ring slot → physical padded row of the backing store
    (identity for a single-device store; the per-shard padded stride for
    a sharded one, matching ``memory_sharded``'s placement)."""
    return (slots // cs) * csp + (slots % cs) if cs else slots


def _gather_candidates(members, assign, scores, cids):
    """Expand routed clusters into a validated candidate slot list.
    Dead probes (score ≤ -2.0: unseeded/sentinel), empty bucket slots,
    and stale member entries (``assign`` no longer points back at the
    probed cluster — ring overwrite or bucket eviction) are all dropped
    by one boolean mask; survivors are unique."""
    P, M = members.shape
    C = assign.shape[0]
    cids_c = jnp.clip(cids, 0, P - 1)
    slots = jnp.take(members, cids_c, axis=0)
    slots = slots.reshape(slots.shape[:-2] + (-1,))          # (..., P'*M)
    owner = jnp.repeat(cids_c, M, axis=-1)
    ok = (jnp.repeat(scores, M, axis=-1) > -2.0) & (slots >= 0)
    ok = ok & (assign[jnp.clip(slots, 0, C - 1)] == owner)
    return slots, owner, ok


@partial(jax.jit, static_argnames=("k", "n_probe", "required", "cs", "csp"))
def _ivf_topk_jit(planes, members, assign, emb, mask, hard, added_at,
                  guide, q, *, k: int, n_probe: int, required: int,
                  cs: int, csp: int) -> mem.TopKResult:
    """Fused single-query IVF read: route → gather → existing top-k
    kernel → packed-meta epilogue, one jitted call (one ``device_get``
    per phase, like the exact path)."""
    C = assign.shape[0]
    scores, cids = _route_merged(planes, q, n_probe)
    slots, owner, ok = _gather_candidates(members, assign, scores, cids)
    # slot-sorted candidates: the scan kernel's local lowest-row
    # tie-break then equals the global (sim desc, slot asc) order
    order = jnp.argsort(jnp.where(ok, slots, jnp.int32(2 ** 30)))
    slots_s = slots[order]
    ok_s = ok[order]
    phys = _phys_rows(jnp.clip(slots_s, 0, C - 1), cs, csp)
    rows = jnp.where(ok_s[:, None], emb[phys], 0.0)
    bits = jnp.where(ok_s, mask[phys, 0], 0)
    L = slots.shape[0]
    Lp = padded_rows(L)
    gmem = jnp.zeros((Lp, emb.shape[1]), jnp.float32).at[:L].set(rows)
    gmask = jnp.zeros((Lp, 1), jnp.int32).at[:L, 0].set(bits)
    sims, lidx = kops.memory_topk_padded(gmem, q, gmask, k, required)
    li = jnp.clip(lidx, 0, L - 1)
    gidx = jnp.clip(slots_s[li], 0, C - 1)
    return mem.TopKResult(sim=sims,
                          meta=mem.pack_meta_parts(gidx, gmask[li, 0],
                                                   hard, added_at, guide))


@partial(jax.jit, static_argnames=("k", "n_probe", "required", "cs", "csp"))
def _ivf_topk_batch_jit(planes, members, assign, emb, mask, hard, added_at,
                        guide, qs, *, k: int, n_probe: int, required: int,
                        cs: int, csp: int) -> mem.TopKResult:
    """Fused multi-query IVF read. Candidate sets differ per query, so
    the selection runs the shared :func:`~repro.kernels.ref._topk_select`
    rounds directly over each query's gathered candidates, keyed by
    global slot — the same total order the store kernels implement.
    Memory is O(B·L·Ep); the wrapper chunks B to bound it."""
    C = assign.shape[0]
    B, E = qs.shape
    scores, cids = _route_merged_batch(planes, qs, n_probe)  # (B, n_probe)
    slots, owner, ok = _gather_candidates(members, assign, scores, cids)
    L = slots.shape[1]
    phys = _phys_rows(jnp.clip(slots, 0, C - 1), cs, csp)
    rows = jnp.where(ok[..., None], emb[phys], 0.0)          # (B, L, Ep)
    bits = jnp.where(ok, mask[phys, 0], 0)                   # (B, L)
    qp = jnp.zeros((B, emb.shape[1]), jnp.float32).at[:, :E].set(
        qs.astype(jnp.float32))
    sims = jnp.einsum("ble,be->bl", rows, qp)
    sims = jnp.where(ok & ((bits & required) == required), sims, -2.0)
    # invalid candidates get distinct above-capacity keys so multiple
    # sentinel rounds keep the -2.0 sim (mirroring the exact scan's
    # distinct masked rows) instead of collapsing to one consumed key
    keys = jnp.where(ok, slots,
                     2 ** 30 + jnp.arange(L, dtype=jnp.int32)[None, :])
    top_s, top_r = ref._topk_select(sims.T, keys.T, k)       # (k, B)
    top_s, top_r = top_s.T, top_r.T
    gidx = jnp.clip(top_r, 0, C - 1)
    hit = keys[:, :, None] == top_r[:, None, :]              # (B, L, k)
    wbits = jnp.sum(bits[:, :, None] * hit, axis=1)
    return mem.TopKResult(sim=top_s,
                          meta=mem.pack_meta_parts(gidx, wbits, hard,
                                                   added_at, guide))


@partial(jax.jit, static_argnames=("n_probe",))
def _route_jit(planes, q, *, n_probe: int):
    return _route_merged(planes, q, n_probe)


@partial(jax.jit, static_argnames=("k", "required", "cs", "csp"))
def _gather_topk_tiered_jit(emb, mask, hard, added_at, guide, slots_s,
                            hot_s, host_rows, host_bits, q, *, k: int,
                            required: int, cs: int, csp: int
                            ) -> mem.TopKResult:
    """Level-2 scan for the offload path: hot candidates gather from the
    device store, cold candidates ride in as the host-mirror gather
    (``host_rows``/``host_bits``, zero where hot). The combined buffer is
    byte-identical to the non-offload gather (the mirror is exact), so
    the result is too."""
    C = hard.shape[0]
    phys = _phys_rows(jnp.clip(slots_s, 0, C - 1), cs, csp)
    rows = jnp.where(hot_s[:, None], emb[phys], 0.0) + host_rows
    bits = jnp.where(hot_s, mask[phys, 0], 0) + host_bits
    L = slots_s.shape[0]
    Lp = padded_rows(L)
    gmem = jnp.zeros((Lp, emb.shape[1]), jnp.float32).at[:L].set(rows)
    gmask = jnp.zeros((Lp, 1), jnp.int32).at[:L, 0].set(bits)
    sims, lidx = kops.memory_topk_padded(gmem, q, gmask, k, required)
    li = jnp.clip(lidx, 0, L - 1)
    gidx = jnp.clip(slots_s[li], 0, C - 1)
    return mem.TopKResult(sim=sims,
                          meta=mem.pack_meta_parts(gidx, gmask[li, 0],
                                                   hard, added_at, guide))


# ---------------------------------------------------------------------------
# The store wrapper
# ---------------------------------------------------------------------------


class IVFMemory:
    """IVF wrapper around a backing store (:class:`MemoryState` or
    :class:`ShardedMemory`), presenting the store *method* API — so the
    :mod:`repro.core.memory` dispatchers, :class:`CommitBuffer`, and
    every controller work against it unchanged. Reads go through the
    two-level path; writes delegate to the backing store and update the
    cluster index incrementally. The backing store stays the exact
    oracle (:meth:`exact_query_topk`).

    Not journal-compatible (the WAL snapshots a raw ``MemoryState``);
    ``RARConfig`` validation rejects the combination up front.
    """

    def __init__(self, store, *, clusters: int, probes: int = 4,
                 bucket_cap: int | None = None, offload: bool = False,
                 cold_after: int = 1024):
        if isinstance(store, IVFMemory):
            raise TypeError("backing store is already IVF-wrapped")
        C = store.capacity
        if not 2 <= clusters <= C:
            raise ValueError(f"retrieval_clusters={clusters} must be in "
                             f"[2, capacity={C}]")
        if not 1 <= probes <= clusters:
            raise ValueError(f"retrieval_probes={probes} must be in "
                             f"[1, clusters={clusters}]")
        self.store = store
        self.clusters = int(clusters)
        self.probes = int(probes)
        self._sharded = isinstance(store, ShardedMemory)
        if self._sharded:
            S = store.shards
            if clusters % S:
                raise ValueError(f"clusters={clusters} not divisible by "
                                 f"{S} shards (cluster c lives with "
                                 f"shard c % S)")
            if probes > clusters // S:
                raise ValueError(f"probes={probes} exceeds the "
                                 f"{clusters // S} clusters per shard")
        self._ep = store.emb.shape[1]
        # a default bucket width follows the capacity, through grow() too
        self._auto_bucket_cap = bucket_cap is None
        self.bucket_cap = self._bucket_cap_for(C, bucket_cap)
        self.offload = bool(offload)
        self.cold_after = int(cold_after)
        self._ptr_host = int(jax.device_get(store.ptr))
        # host-side index state (numpy; mutated on the learn path only)
        self._cent = np.zeros((self.clusters, self._ep), np.float32)
        self._csum = np.zeros((self.clusters, self._ep), np.float32)
        self._ccount = np.zeros(self.clusters, np.int64)
        self._seeded = 0
        self._assign = np.full(C, -1, np.int32)
        self._members = np.full((self.clusters, self.bucket_cap), -1,
                                np.int32)
        self._mptr = np.zeros(self.clusters, np.int64)
        if self.offload:
            self._emb_host = np.zeros((C, self._ep), np.float32)
            self._bits_host = np.zeros(C, np.int32)
            self._last_probe = np.zeros(self.clusters, np.int64)
            self._tier_hot = np.ones(self.clusters, bool)
        # stats (host counters, no device syncs)
        self.bucket_evictions = 0
        self.reindexes = 0
        self.host_fetch_rows = 0
        self.device_fetch_rows = 0
        self._qcount = 0
        self._dirty = True
        self._planes = None
        self._members_dev = None
        self._assign_dev = None
        if self._ptr_host:
            self.reindex()

    def _bucket_cap_for(self, capacity: int, bucket_cap: int | None) -> int:
        if bucket_cap is None:
            # ~4x the average cluster occupancy of a full ring: skewed
            # clusters overflow (FIFO bucket eviction) only past that
            bucket_cap = max(8, math.ceil(4 * capacity / self.clusters))
        return _round_up(int(bucket_cap), 8)

    # -- delegation -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.store.capacity

    @property
    def guide(self):
        return self.store.guide

    @property
    def hard(self):
        return self.store.hard

    @property
    def added_at(self):
        return self.store.added_at

    @property
    def valid(self):
        return self.store.valid

    @property
    def has_guide(self):
        return self.store.has_guide

    @property
    def ptr(self):
        return self.store.ptr

    @property
    def size_fast(self) -> int:
        return min(self._ptr_host, self.capacity)

    def debug_size(self) -> int:
        return self.store.debug_size()

    # -- index maintenance ----------------------------------------------
    def _ivf_add(self, X: np.ndarray, slots: np.ndarray) -> None:
        """Online k-means + bucket update for K new rows landing at ring
        ``slots``. Assignment scores use the batch-start centroids
        (minibatch k-means); centroid running means update sequentially.
        """
        P, M = self.clusters, self.bucket_cap
        nearest = (np.argmax(X @ self._cent.T, axis=1)
                   if self._seeded == P else None)
        for j in range(X.shape[0]):
            slot = int(slots[j])
            x = X[j]
            if self._seeded < P:
                c = self._seeded        # round-robin seeding
                self._seeded += 1
            elif nearest is not None:
                c = int(nearest[j])
            else:
                c = int(np.argmax(self._cent[:self._seeded] @ x))
            self._csum[c] += x
            self._ccount[c] += 1
            m = self._csum[c] / self._ccount[c]
            n = float(np.linalg.norm(m))
            self._cent[c] = m / n if n > 0.0 else m
            prev = int(self._assign[slot])
            if prev >= 0:               # ring overwrite: unbucket first
                b = self._members[prev]
                b[b == slot] = -1
            row = self._members[c]
            pos = int(self._mptr[c]) % M
            old = int(row[pos])
            if old >= 0 and old != slot:
                self._assign[old] = -1  # bucket overflow: evict oldest
                self.bucket_evictions += 1
            row[pos] = slot
            self._mptr[c] += 1
            self._assign[slot] = c
        self._dirty = True

    def _logical_rows(self):
        st = self.store
        if self._sharded:
            phys = _phys_rows(jnp.arange(st.capacity, dtype=jnp.int32),
                              st.cs, st.csp)
            return jnp.asarray(st.emb)[phys], jnp.asarray(st.mask)[phys, 0]
        return st.emb[:st.capacity], st.mask[:st.capacity, 0]

    def reindex(self) -> None:
        """Rebuild the whole index from the backing store: vectorized
        k-means (round-robin seeding from the oldest valid rows, two
        refinement sweeps once fully seeded) + bucket rebuild keeping
        each cluster's newest ``bucket_cap`` members. One bulk store
        transfer — runs at attach/grow time, never per query."""
        C, P, M = self.capacity, self.clusters, self.bucket_cap
        emb, bits = jax.device_get(self._logical_rows())
        emb = np.asarray(emb, np.float32)
        bits = np.asarray(bits, np.int32)
        if self.offload:
            self._emb_host[:] = emb
            self._bits_host[:] = bits
        self._assign = np.full(C, -1, np.int32)
        self._members = np.full((P, M), -1, np.int32)
        self._mptr = np.zeros(P, np.int64)
        self._csum = np.zeros((P, self._ep), np.float32)
        self._ccount = np.zeros(P, np.int64)
        self._cent = np.zeros((P, self._ep), np.float32)
        self.reindexes += 1
        self._dirty = True
        slot = np.arange(C)
        vs = slot[(bits & MASK_VALID) != 0]
        if not len(vs):
            self._seeded = 0
            return
        ptr = self._ptr_host
        age = slot if ptr <= C else (slot - ptr) % C
        vs = vs[np.argsort(age[vs], kind="stable")]          # oldest first
        X = emb[vs]
        self._seeded = min(P, len(vs))
        s = self._seeded
        cent = X[:s].copy()
        a = np.zeros(len(vs), np.int64)
        sweeps = 2 if s == P else 1
        for _ in range(sweeps + 1):
            a = np.argmax(X @ cent.T, axis=1)
            csum = np.zeros((s, self._ep), np.float32)
            np.add.at(csum, a, X)
            cc = np.bincount(a, minlength=s)
            nz = cc > 0
            cent[nz] = csum[nz] / cc[nz, None]
            norms = np.linalg.norm(cent, axis=1)
            cent[norms > 0] /= norms[norms > 0, None]
        self._cent[:s] = cent
        self._csum[:s] = csum
        self._ccount[:s] = cc
        for c in range(s):
            ms = vs[a == c]                                  # oldest first
            if len(ms) > M:
                self.bucket_evictions += len(ms) - M
                ms = ms[-M:]
            self._members[c, :len(ms)] = ms
            self._mptr[c] = len(ms)
            self._assign[ms] = c

    def _refresh(self) -> None:
        """Lazy device-mirror upload: centroid plane(s) in padded kernel
        layout (per-shard subsets when sharded) + member/assign tables.
        O(P·Ep + P·M) once per index mutation, off the per-query path."""
        if not self._dirty:
            return
        P, Ep = self.clusters, self._ep
        live = self._ccount > 0
        if self._sharded:
            S = self.store.shards
            groups = [np.flatnonzero(np.arange(P) % S == s).astype(np.int32)
                      for s in range(S)]
        else:
            groups = [np.arange(P, dtype=np.int32)]
        planes = []
        for cid in groups:
            ps = len(cid)
            psp = padded_rows(ps)
            cent = np.zeros((psp, Ep), np.float32)
            cent[:ps] = self._cent[cid]
            cm = np.zeros((psp, 1), np.int32)
            cm[:ps, 0] = np.where(live[cid], MASK_VALID, 0)
            planes.append((jnp.asarray(cent), jnp.asarray(cm),
                           jnp.asarray(cid)))
        self._planes = tuple(planes)
        self._members_dev = jnp.asarray(self._members)
        self._assign_dev = jnp.asarray(self._assign)
        self._dirty = False

    # -- reads ----------------------------------------------------------
    def _geometry(self) -> tuple[int, int]:
        if self._sharded:
            return self.store.cs, self.store.csp
        return 0, 0

    def _check_topk(self, k: int) -> None:
        mem._check_k(k, self.capacity)
        budget = self.probes * self.bucket_cap
        if k > budget:
            raise ValueError(f"retrieval k={k} exceeds the probed "
                             f"candidate budget {budget} "
                             f"({self.probes} probes x {self.bucket_cap} "
                             f"bucket rows); raise probes or bucket_cap")

    def query_topk(self, emb: jax.Array, k: int,
                   guides_only: bool = False) -> mem.TopKResult:
        self._check_topk(k)
        self._refresh()
        if self.offload:
            return self._query_topk_tiered(emb, k, guides_only)
        self._qcount += 1
        cs, csp = self._geometry()
        st = self.store
        return _ivf_topk_jit(self._planes, self._members_dev,
                             self._assign_dev, st.emb, st.mask, st.hard,
                             st.added_at, st.guide, jnp.asarray(emb),
                             k=k, n_probe=self.probes,
                             required=mem.required_bits(guides_only),
                             cs=cs, csp=csp)

    def query_topk_batch(self, embs: jax.Array, k: int,
                         guides_only: bool = False,
                         _chunk: int = 8) -> mem.TopKResult:
        self._check_topk(k)
        self._refresh()
        cs, csp = self._geometry()
        st = self.store
        embs = jnp.asarray(embs)
        B = embs.shape[0]
        self._qcount += B
        outs = [_ivf_topk_batch_jit(self._planes, self._members_dev,
                                    self._assign_dev, st.emb, st.mask,
                                    st.hard, st.added_at, st.guide,
                                    embs[i:i + _chunk], k=k,
                                    n_probe=self.probes,
                                    required=mem.required_bits(guides_only),
                                    cs=cs, csp=csp)
                for i in range(0, B, _chunk)]
        if len(outs) == 1:
            return outs[0]
        return mem.TopKResult(sim=jnp.concatenate([o.sim for o in outs]),
                              meta=jnp.concatenate([o.meta for o in outs]))

    def query(self, emb: jax.Array,
              guides_only: bool = False) -> mem.QueryResult:
        r = self.query_topk(emb, 1, guides_only=guides_only)
        return mem.QueryResult(sim=r.sim[..., 0], meta=r.meta[..., 0, :])

    def query_batch(self, embs: jax.Array,
                    guides_only: bool = False) -> mem.QueryResult:
        r = self.query_topk_batch(embs, 1, guides_only=guides_only)
        return mem.QueryResult(sim=r.sim[..., 0], meta=r.meta[..., 0, :])

    def _query_topk_tiered(self, emb: jax.Array, k: int,
                           guides_only: bool) -> mem.TopKResult:
        """Offload read: route on device, sync the routed cluster ids
        (the one extra transfer the tiering costs), gather cold
        candidates from the host mirror and hot ones on-device."""
        q = jnp.asarray(emb)
        scores, cids = jax.device_get(
            _route_jit(self._planes, q, n_probe=self.probes))
        P, M, C = self.clusters, self.bucket_cap, self.capacity
        cids_c = np.clip(np.asarray(cids), 0, P - 1)
        live = np.asarray(scores) > -2.0
        # tier decision uses the state *before* this query's probes: a
        # cold cluster routed to now pays its host fetch this once, then
        # becomes hot for subsequent queries
        self._tier_hot = self._last_probe > (self._qcount -
                                             self.cold_after)
        self._last_probe[cids_c[live]] = self._qcount
        slots = self._members[cids_c].reshape(-1)
        owner = np.repeat(cids_c, M)
        ok = np.repeat(live, M) & (slots >= 0)
        ok &= self._assign[np.clip(slots, 0, C - 1)] == owner
        order = np.argsort(np.where(ok, slots, 2 ** 30), kind="stable")
        slots_s = slots[order]
        ok_s = ok[order]
        hot_s = ok_s & self._tier_hot[owner[order]]
        cold_s = ok_s & ~hot_s
        safe = np.clip(slots_s, 0, C - 1)
        host_rows = np.where(cold_s[:, None], self._emb_host[safe], 0.0)
        host_bits = np.where(cold_s, self._bits_host[safe], 0)
        self.host_fetch_rows += int(cold_s.sum())
        self.device_fetch_rows += int(hot_s.sum())
        self._qcount += 1
        cs, csp = self._geometry()
        st = self.store
        return _gather_topk_tiered_jit(
            st.emb, st.mask, st.hard, st.added_at, st.guide,
            jnp.asarray(slots_s, jnp.int32), jnp.asarray(hot_s),
            jnp.asarray(host_rows, jnp.float32),
            jnp.asarray(host_bits, jnp.int32), q, k=k,
            required=mem.required_bits(guides_only), cs=cs, csp=csp)

    # -- exact oracle ---------------------------------------------------
    def exact_query_topk(self, emb: jax.Array, k: int,
                         guides_only: bool = False) -> mem.TopKResult:
        """The exhaustive O(C) scan over the backing store — the recall
        oracle and fallback."""
        return mem.query_topk(self.store, emb, k, guides_only=guides_only)

    def exact_query_topk_batch(self, embs: jax.Array, k: int,
                               guides_only: bool = False) -> mem.TopKResult:
        return mem.query_topk_batch(self.store, embs, k,
                                    guides_only=guides_only)

    # -- writes ---------------------------------------------------------
    def add(self, emb, guide, has_guide, hard, now) -> None:
        self.add_batch(jnp.asarray(emb)[None], jnp.asarray(guide)[None],
                       jnp.asarray([has_guide]), jnp.asarray([hard]),
                       jnp.asarray([now], jnp.int32))

    def add_batch(self, embs, guides, has_guide, hard, now) -> None:
        K, C = embs.shape[0], self.capacity
        self.store = mem.add_batch(self.store, embs, guides, has_guide,
                                   hard, now)
        slots = (self._ptr_host + np.arange(K)) % C
        self._ptr_host += K
        # host copy of the committed rows (learn-path transfer, same
        # drain the store scatter runs on — never the serve path)
        X = np.asarray(jax.device_get(jnp.asarray(embs)), np.float32)
        if X.shape[1] < self._ep:
            X = np.pad(X, ((0, 0), (0, self._ep - X.shape[1])))
        if self.offload:
            hg = np.asarray(jax.device_get(jnp.asarray(has_guide)), bool)
            self._emb_host[slots] = X
            self._bits_host[slots] = np.where(hg, 3, 1)  # VALID|GUIDE
        self._ivf_add(X, slots)

    def mark_soft(self, index) -> None:
        self.store = mem.mark_soft(self.store, index)

    def touch(self, index, now) -> None:
        self.store = mem.touch(self.store, index, now)

    # -- grow-in-place --------------------------------------------------
    def grow(self, new_capacity: int):
        """Grow the backing store (:func:`repro.core.memory.grow_memory`)
        and re-bucket the clusters against the re-laid-out slots (a
        default bucket width widens with the capacity, so probing every
        cluster stays the exact scan).
        Returns ``(self, remap)`` — the :meth:`CommitStream.grow`
        contract."""
        if self._sharded:
            raise NotImplementedError(
                "grow over a sharded backing store is not supported")
        self.store, remap = mem.grow_memory(self.store, new_capacity)
        self._ptr_host = int(jax.device_get(self.store.ptr))
        C = self.store.capacity
        if self._auto_bucket_cap:
            self.bucket_cap = self._bucket_cap_for(C, None)
        self._assign = np.full(C, -1, np.int32)
        if self.offload:
            self._emb_host = np.zeros((C, self._ep), np.float32)
            self._bits_host = np.zeros(C, np.int32)
        self.reindex()
        return self, remap

    # -- introspection --------------------------------------------------
    def stats(self) -> dict:
        """Host-counter snapshot (no device syncs)."""
        out = {
            "clusters": self.clusters,
            "probes": self.probes,
            "bucket_cap": self.bucket_cap,
            "seeded": int(self._seeded),
            "indexed": int((self._assign >= 0).sum()),
            "bucket_evictions": self.bucket_evictions,
            "reindexes": self.reindexes,
            "queries": self._qcount,
        }
        if self.offload:
            out.update(hot_clusters=int(self._tier_hot.sum()),
                       cold_clusters=int((~self._tier_hot).sum()),
                       host_fetch_rows=self.host_fetch_rows,
                       device_fetch_rows=self.device_fetch_rows)
        return out


def wrap_store(store, cfg):
    """Apply a :class:`RARConfig`'s retrieval knobs to a freshly built
    (or injected) store: identity when IVF is off
    (``retrieval_clusters == 0``, the default) or the store is already
    wrapped — the construction sites (``RAR.__init__``, the serving
    fabrics) all route through here so a shared store is wrapped exactly
    once."""
    clusters = getattr(cfg, "retrieval_clusters", 0)
    if not clusters or isinstance(store, IVFMemory):
        return store
    return IVFMemory(store, clusters=clusters,
                     probes=getattr(cfg, "retrieval_probes", 4))
