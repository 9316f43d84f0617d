"""Skill & guide memory — the paper's vector DB (§III-F), device-resident.

A fixed-capacity ring of request embeddings with per-entry metadata:

* ``has_guide`` — entry stores a guide (Case 2) vs. a bare skill (Case 1),
* ``hard``     — weak FM failed even with guides (Case 3): route strong,
* ``added_at`` — logical time of insertion (drives Case-3 re-probing),
* ``guide``    — fixed-width guide token block.

Persistent padded layout (the zero-copy invariant)
--------------------------------------------------
``emb`` lives **permanently in kernel layout**: (Cp, Ep) f32 with rows
padded to the kernel block multiple and lanes to a multiple of 128
(:func:`repro.kernels.memory_topk.padded_rows` /
:func:`~repro.kernels.memory_topk.padded_lanes`). ``valid`` and
``has_guide`` are packed into an incrementally-maintained (Cp, 1) int32
``mask`` bit plane (bit 0 = valid, bit 1 = has_guide). Logical ring slots
are rows [0, C) of the padded buffers; padding rows [C, Cp) carry mask 0
and are never valid.

Consequences:

* a query touches each store byte exactly once — the kernel consumes the
  buffers as-is, with no per-call O(C·E) re-padding copy (the old wrappers
  re-materialized the store on *every* query, doubling HBM traffic);
* the ``guides_only`` view is a different ``required`` bit set on the same
  mask plane — no per-query (C,) mask combine;
* writes scatter directly into the padded buffers. A drain epoch
  applied through :class:`CommitStream` is one program that donates the
  store, so it updates the buffers in place, O(K·E) per commit. The
  value-semantics calls (:func:`add`, :func:`add_batch`,
  :func:`mark_soft`, :func:`touch`, a direct :meth:`CommitBuffer.apply`)
  leave their input store valid, so XLA copies the store first: O(C·E).

Static shapes keep every operation jit-compatible; the similarity search
is a fused cosine/top-1 over the full store — the Pallas kernel in
:mod:`repro.kernels.memory_topk` implements the contract blocked for VMEM,
and :func:`query` routes through its jnp reference on CPU. The query
epilogue (metadata gathers + ``guides_only`` handling) is fused into the
same jitted call and returns a :class:`QueryResult` packing everything
into two arrays — one ``device_get`` moves a whole microbatch of results
to the host. :func:`query_topk` / :func:`query_topk_batch` widen the same
single-pass read to the top-k entries (packed :class:`TopKResult`, sorted
by sim desc / row asc; k = 1 is bit-identical to the top-1 read) — the
multi-guide serving path. Eviction is FIFO (ring pointer), the capacity
is a config knob. :mod:`repro.core.memory_sharded` scales the same
contract across devices.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.memory_topk import (DEFAULT_BLOCK_C, MASK_GUIDE,
                                       MASK_VALID, padded_lanes,
                                       padded_rows)
from repro.serving.metrics import count_syncs, span


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    capacity: int = 4096
    embed_dim: int = 384
    guide_len: int = 8


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MemoryState:
    emb: jax.Array       # (Cp, Ep) f32 — persistent kernel layout; logical
    #                      rows [0, C), L2-normalized (or zero), zero padding
    mask: jax.Array      # (Cp, 1) int32 bit plane: MASK_VALID | MASK_GUIDE
    guide: jax.Array     # (C, G) int32
    hard: jax.Array      # (C,) bool
    added_at: jax.Array  # (C,) int32 logical time
    ptr: jax.Array       # () int32 ring insert pointer

    @property
    def capacity(self) -> int:
        """Logical capacity C (the padded buffers hold Cp ≥ C rows)."""
        return self.hard.shape[0]

    @property
    def valid(self) -> jax.Array:
        """(C,) bool view decoded from the mask bit plane."""
        return (self.mask[:self.capacity, 0] & MASK_VALID) != 0

    @property
    def has_guide(self) -> jax.Array:
        """(C,) bool view decoded from the mask bit plane."""
        return (self.mask[:self.capacity, 0] & MASK_GUIDE) != 0

    def debug_size(self) -> int:
        """Debugging-only occupancy: a *blocking device sync* (full
        reduction over ``valid``). Deliberately a method, not a property,
        so the sync is loud at call sites — hot paths must use
        :attr:`size_fast` or the commit-stream counters instead.

        Query-path sync audit (the PR-4 host-counter contract): the serve
        path performs exactly **one** device transfer per controller
        phase — the packed :meth:`QueryResult.device_get` /
        :meth:`TopKResult.device_get`. Every other host-visible number is
        a host counter: occupancy via ``CommitStream.commits`` +
        ``RAR._ptr_base`` (one ``int(ptr)`` at construction, never per
        request), epoch progress via ``CommitBuffer.epoch``/
        ``entries_applied``. A :class:`CommitStream` keeps a host mirror
        of the ring pointer (one ``device_get`` when it first applies, and
        one per grow), so a drain epoch reads no pointer either; only
        callers without a stream (journal recovery, a direct
        :meth:`CommitBuffer.apply`) read it per epoch, and
        :attr:`size_fast` transfers one scalar for shutdown/CLI reporting
        only."""
        return int(jnp.sum(self.valid))

    @property
    def size_fast(self) -> int:
        """O(1) occupancy from the ring pointer: entries are only ever
        added (``valid`` is monotone), so size == min(ptr, capacity).
        Transfers one scalar instead of reducing the (C,) mask — still a
        device sync; keep it off per-request paths (see
        :meth:`debug_size` for the full audit)."""
        return min(int(self.ptr), self.capacity)


def init_memory(cfg: MemoryConfig) -> MemoryState:
    C, E, G = cfg.capacity, cfg.embed_dim, cfg.guide_len
    Cp, Ep = padded_rows(C), padded_lanes(E)
    return MemoryState(
        emb=jnp.zeros((Cp, Ep), jnp.float32),
        mask=jnp.zeros((Cp, 1), jnp.int32),
        guide=jnp.zeros((C, G), jnp.int32),
        hard=jnp.zeros((C,), bool),
        added_at=jnp.zeros((C,), jnp.int32),
        ptr=jnp.zeros((), jnp.int32),
    )


def _pad_lanes(embs: jax.Array, ep: int) -> jax.Array:
    """(…, E) → (…, Ep): zero-pad the lane dim. O(K·E) — commit-sized,
    never store-sized."""
    pad = [(0, 0)] * (embs.ndim - 1) + [(0, ep - embs.shape[-1])]
    return jnp.pad(embs.astype(jnp.float32), pad)


def _mask_bits(has_guide: jax.Array) -> jax.Array:
    return MASK_VALID + jnp.where(has_guide, MASK_GUIDE, 0).astype(jnp.int32)


@jax.jit
def _add_jit(state: MemoryState, emb: jax.Array, guide: jax.Array,
             has_guide: jax.Array, hard: jax.Array,
             now: jax.Array) -> MemoryState:
    i = state.ptr % state.capacity
    return MemoryState(
        emb=state.emb.at[i].set(_pad_lanes(emb, state.emb.shape[1])),
        mask=state.mask.at[i, 0].set(_mask_bits(has_guide)),
        guide=state.guide.at[i].set(guide),
        hard=state.hard.at[i].set(hard),
        added_at=state.added_at.at[i].set(now),
        ptr=state.ptr + 1,
    )


@jax.jit
def _add_batch_jit(state: MemoryState, embs: jax.Array, guides: jax.Array,
                   has_guide: jax.Array, hard: jax.Array,
                   now: jax.Array) -> MemoryState:
    K, C = embs.shape[0], state.capacity
    if K > C:
        raise ValueError(f"microbatch commit of {K} entries exceeds "
                         f"memory capacity {C}")
    idx = (state.ptr + jnp.arange(K, dtype=jnp.int32)) % C
    return MemoryState(
        emb=state.emb.at[idx].set(_pad_lanes(embs, state.emb.shape[1])),
        mask=state.mask.at[idx, 0].set(_mask_bits(has_guide)),
        guide=state.guide.at[idx].set(guides),
        hard=state.hard.at[idx].set(hard),
        added_at=state.added_at.at[idx].set(now),
        ptr=state.ptr + K,
    )


class _MetaViews:
    """Per-field views over the packed int32 ``meta`` epilogue
    [index, has_guide, hard, added_at, guide₀…guide_{G-1}]; work on device
    arrays and host numpy alike, for any leading shape."""

    @property
    def index(self):
        return self.meta[..., 0]

    @property
    def has_guide(self):
        return self.meta[..., 1].astype(bool)

    @property
    def hard(self):
        return self.meta[..., 2].astype(bool)

    @property
    def added_at(self):
        return self.meta[..., 3]

    @property
    def guide(self):
        return self.meta[..., 4:]

    def device_get(self):
        """Pull the whole result to the host in one transfer."""
        sim, meta = jax.device_get((self.sim, self.meta))
        return type(self)(sim, meta)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QueryResult(_MetaViews):
    """Top-1 result with its metadata epilogue fused into two arrays.

    ``sim`` is (…,) f32; ``meta`` is (…, 4 + G) int32 packing
    [index, has_guide, hard, added_at, guide₀…guide_{G-1}] — a single
    host-transferable struct (one :meth:`device_get` per microbatch phase
    instead of ~6 per-field transfers)."""
    sim: jax.Array        # (…,) f32 cosine of best row (-2 if view empty)
    meta: jax.Array       # (…, 4 + G) int32 packed epilogue


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TopKResult(_MetaViews):
    """Top-k result — the multi-guide read path's packed struct.

    ``sim`` is (…, k) f32 and ``meta`` is (…, k, 4 + G) int32, entries
    sorted by (sim desc, store row asc); entries past the view's
    population carry the -2.0 sentinel. Same one-host-transfer contract
    as :class:`QueryResult` (one :meth:`device_get` per controller
    phase); the field views gain a trailing k axis."""
    sim: jax.Array        # (…, k) f32
    meta: jax.Array       # (…, k, 4 + G) int32


def pack_meta_parts(idx: jax.Array, bits: jax.Array, hard: jax.Array,
                    added_at: jax.Array, guide: jax.Array) -> jax.Array:
    """THE packed-meta layout — [index, has_guide, hard, added_at,
    guide₀…] — single source of truth for every store flavour. ``bits``
    are the winning rows' mask-plane values; ``hard``/``added_at``/
    ``guide`` are gathered here by ``idx``."""
    head = jnp.stack([idx.astype(jnp.int32),
                      (bits & MASK_GUIDE) // MASK_GUIDE,
                      hard[idx].astype(jnp.int32),
                      added_at[idx]], axis=-1)
    return jnp.concatenate([head, guide[idx]], axis=-1)


# the sharded store's epilogue dispatch (its kernel+combine is a separate
# shard_map jit; this keeps the metadata gathers one fused call, not ~5
# eager ops per query)
pack_meta_jit = jax.jit(pack_meta_parts)


def pack_meta(state: MemoryState, idx: jax.Array) -> jax.Array:
    """Fused query epilogue: gather the metadata of row(s) ``idx`` into the
    packed int32 struct (called inside the jitted query)."""
    return pack_meta_parts(idx, state.mask[idx, 0], state.hard,
                           state.added_at, state.guide)


def required_bits(guides_only: bool) -> int:
    """Mask-plane bit set a row must carry to join the query's view."""
    return MASK_VALID | (MASK_GUIDE if guides_only else 0)


@partial(jax.jit, static_argnames=("guides_only",))
def _query_jit(state: MemoryState, emb: jax.Array,
               guides_only: bool = False) -> QueryResult:
    sims, idx = kops.memory_top1_padded(state.emb, emb, state.mask,
                                        required_bits(guides_only))
    return QueryResult(sim=sims, meta=pack_meta(state, idx))


@partial(jax.jit, static_argnames=("guides_only",))
def _query_batch_jit(state: MemoryState, embs: jax.Array,
                     guides_only: bool = False) -> QueryResult:
    sims, idx = kops.memory_top1_batch_padded(state.emb, embs, state.mask,
                                              required_bits(guides_only))
    return QueryResult(sim=sims, meta=pack_meta(state, idx))


@partial(jax.jit, static_argnames=("k", "guides_only"))
def _query_topk_jit(state: MemoryState, emb: jax.Array, k: int,
                    guides_only: bool = False) -> TopKResult:
    sims, idx = kops.memory_topk_padded(state.emb, emb, state.mask, k,
                                        required_bits(guides_only))
    return TopKResult(sim=sims, meta=pack_meta(state, idx))


@partial(jax.jit, static_argnames=("k", "guides_only"))
def _query_topk_batch_jit(state: MemoryState, embs: jax.Array, k: int,
                          guides_only: bool = False) -> TopKResult:
    sims, idx = kops.memory_topk_batch_padded(state.emb, embs, state.mask,
                                              k, required_bits(guides_only))
    return TopKResult(sim=sims, meta=pack_meta(state, idx))


def grow_memory(state: MemoryState, new_capacity: int
                ) -> tuple[MemoryState, "jax.Array"]:
    """Grow-in-place capacity re-layout: returns ``(grown_state, remap)``
    where ``remap[s]`` is the new logical slot of old slot ``s``.

    Two regimes, chosen by whether the ring has wrapped:

    * **Not yet wrapped** (``ptr <= C``) — rows copy straight across:
      slot indices, the ring pointer, and therefore every outstanding
      ``ptr_snapshot`` eviction guard in :class:`CommitBuffer` stay
      *exactly* valid (the guard's modulo moves from C to newC, but with
      ``snap <= ptr <= C`` the covered-interval test is unchanged for
      every slot). ``remap`` is the identity.
    * **Wrapped** (``ptr > C``) — the ring is linearized oldest-first
      (old slot ``ptr % C`` becomes row 0) and the new pointer is C, so
      future inserts land after the newest entry and FIFO eviction order
      is preserved. Old slot indices *move* (by ``remap``), so callers
      must quiesce first: :meth:`CommitStream.grow` refuses while ops
      are staged, and rebases each subscribed view's ``_ptr_base`` so
      post-grow pointer snapshots are exact. Flag ops captured before a
      wrapped grow are the caller's to remap (or drop — the guard's
      snapshot clamp makes a stale op at worst a conservatively dropped
      flag update, never a corrupted entry).

    Runs off the serve path (one ``device_get`` of the scalar pointer);
    the copy is O(C·E) once, like ``to_padded_layout``.
    """
    C = state.capacity
    if new_capacity < C:
        raise ValueError(f"cannot shrink memory: {new_capacity} < {C}")
    G = state.guide.shape[1]
    ptr = int(jax.device_get(state.ptr))
    fresh = init_memory(MemoryConfig(capacity=new_capacity,
                                     embed_dim=state.emb.shape[1],
                                     guide_len=G))
    if ptr <= C:
        order = jnp.arange(C, dtype=jnp.int32)
        # a buffer of its own: a later donating commit of the grown
        # store must not delete the old store's pointer
        new_ptr = jnp.asarray(ptr, jnp.int32)
        remap = jnp.arange(C, dtype=jnp.int32)
    else:
        shift = ptr % C
        order = (jnp.arange(C, dtype=jnp.int32) + shift) % C
        new_ptr = jnp.asarray(C, jnp.int32)
        remap = (jnp.arange(C, dtype=jnp.int32) - shift) % C
    grown = MemoryState(
        emb=fresh.emb.at[:C].set(state.emb[order]),
        mask=fresh.mask.at[:C].set(state.mask[order]),
        guide=fresh.guide.at[:C].set(state.guide[order]),
        hard=fresh.hard.at[:C].set(state.hard[order]),
        added_at=fresh.added_at.at[:C].set(state.added_at[order]),
        ptr=new_ptr,
    )
    return grown, remap


@jax.jit
def _mark_soft_jit(state: MemoryState, index: jax.Array) -> MemoryState:
    return dataclasses.replace(state, hard=state.hard.at[index].set(False))


@jax.jit
def _touch_jit(state: MemoryState, index: jax.Array,
               now: jax.Array) -> MemoryState:
    return dataclasses.replace(state,
                               added_at=state.added_at.at[index].set(now))


#: fewest rows an epoch's operand matrix is padded to: with microbatches
#: of up to 8 requests every inline drain epoch runs one program
_EPOCH_MIN_ROWS = 8


def _epoch_operands(state, records, softs, touches):
    """Pack one epoch's ops into the single int32 operand of
    :func:`_epoch_body` (one host→device transfer). Row r holds insert r
    — [emb bits (Ep) | guide (G) | mask bits | hard | now] — then
    soft-clear r's index and touch r's (index, now). Rows are a
    power-of-two bucket of at least :data:`_EPOCH_MIN_ROWS`; a kind's
    rows past its count are padding: mask bits 0 for an insert, index C
    (one past the ring, dropped by the scatter) for a flag op."""
    import numpy as np

    Ep, C, G = state.emb.shape[1], state.capacity, state.guide.shape[1]
    n = max(len(records), len(softs), len(touches))
    rows = max(_EPOCH_MIN_ROWS, 1 << (n - 1).bit_length())
    ops = np.zeros((rows, Ep + G + 6), np.int32)
    k = len(records)
    if k:
        embs = np.stack([np.asarray(r[1], np.float32) for r in records])
        ops[:k, :embs.shape[1]] = embs.view(np.int32)
        ops[:k, Ep:Ep + G] = np.stack([np.asarray(r[2], np.int32)
                                       for r in records])
        ops[:k, Ep + G] = [MASK_VALID | (MASK_GUIDE if r[3] else 0)
                           for r in records]
        ops[:k, Ep + G + 1] = [r[4] for r in records]
        ops[:k, Ep + G + 2] = [r[0] for r in records]
    ops[:, Ep + G + 3:Ep + G + 5] = C
    ops[:len(softs), Ep + G + 3] = softs
    ops[:len(touches), Ep + G + 4] = list(touches)
    ops[:len(touches), Ep + G + 5] = list(touches.values())
    return ops


def _epoch_body(state: MemoryState, ops: jax.Array) -> MemoryState:
    """One drain epoch as one program, in the value-semantics order:
    inserts at ``(ptr + i) % C`` in ring order, then the soft-clears,
    then the touches; ``ptr`` advances by the real insert count (rows
    with mask bits, which always carry ``MASK_VALID``). Padding rows
    scatter out of range and are dropped. ``ops`` as
    :func:`_epoch_operands` packs it; at most C inserts per call."""
    Cp, Ep = state.emb.shape
    C, G = state.capacity, state.guide.shape[1]
    meta = ops[:, Ep:]
    bits = meta[:, G]
    row = jnp.arange(ops.shape[0], dtype=jnp.int32)
    slot = jnp.where(bits != 0, (state.ptr + row) % C, Cp)

    def put(a, index, value):
        return a.at[index].set(value, mode="drop")

    return MemoryState(
        emb=put(state.emb, slot,
                jax.lax.bitcast_convert_type(ops[:, :Ep], jnp.float32)),
        mask=state.mask.at[slot, 0].set(bits, mode="drop"),
        guide=put(state.guide, slot, meta[:, :G]),
        hard=put(put(state.hard, slot, meta[:, G + 1] != 0),
                 meta[:, G + 3], False),
        added_at=put(put(state.added_at, slot, meta[:, G + 2]),
                     meta[:, G + 4], meta[:, G + 5]),
        ptr=state.ptr + jnp.sum(bits != 0, dtype=jnp.int32),
    )


# one body, two programs: the stream's path gives its store up (the
# buffers are updated in place); every other caller keeps its input
_epoch_jit = jax.jit(_epoch_body)
_epoch_donated_jit = jax.jit(_epoch_body, donate_argnums=0)


# ---------------------------------------------------------------------------
# Epoch-versioned commit buffer — the shadow plane's write staging area
# ---------------------------------------------------------------------------


class CommitBuffer:
    """Staging area for shadow-plane memory writes, applied in epochs.

    The async shadow queue (:mod:`repro.core.shadow`) decouples learning
    (weak probes, guide generation, memory commits) from the serve sweep.
    All memory *writes* it produces are staged here — inserts
    (:meth:`stage_add`), re-probe flag clears (:meth:`stage_soft_clear`)
    and timestamp refreshes (:meth:`stage_touch`) — and land on the store
    in one :meth:`apply` call per drain **epoch**:

    * **Atomicity** — within an epoch, all staged writes become visible
      together. For the functional :class:`MemoryState` the new store is
      built first and swapped in as one reference assignment; for the
      mutable sharded store the caller serializes :meth:`apply` against
      readers (the shadow queue's ``store_lock``). A concurrent query can
      therefore never observe a partially-applied shadow batch (the
      hypothesis sweep in ``tests/test_shadow.py`` pins this).
    * **Determinism / order-independence** — staged ops are keyed by
      their request's logical time ``now`` (unique per request) and are
      sorted before applying: inserts by ``now`` (FIFO ring order — the
      same order the sequential controller would have written them),
      soft-clears as a sorted index set, touches last-``now``-wins per
      index. The final store state of an epoch is thus independent of the
      order items were staged in.
    * **Eviction guard** — flag updates target entries that existed when
      their request was classified; a flag update is dropped if its slot
      has been overwritten by any FIFO insert since then (it would
      otherwise hit the unrelated fresh entry now in that slot). The
      staging calls take the ring pointer observed at classification time
      (``ptr_snapshot``) so the guard spans *intervening* drain epochs,
      not just the applying epoch's own scatter — with no intervening
      drains (inline / deferred flush-every-batch) this reduces exactly
      to the PR-1 microbatch-commit rule.
    * **Transfer-free accounting** — :attr:`entries_applied` counts
      inserts ever applied on the host, so serve-loop progress logging
      can report ring occupancy without the ``size_fast`` device-scalar
      sync.
    * **Who donates** — a :class:`MemoryState` takes an epoch as one
      fused program. :meth:`apply` keeps value semantics (the input store
      stays valid, so XLA copies it). :meth:`apply_ops` with
      ``donate=True`` gives the store up and updates it in place: only
      an owner of the store passes it — :class:`CommitStream`, the
      process fabric's worker mirror, journal replay — and never reads
      the input again.

    Single-writer discipline: one thread stages and applies at a time
    (the drainer); readers only need :attr:`epoch`/:attr:`entries_applied`
    which are plain ints under the GIL.
    """

    def __init__(self):
        self._records: list[tuple] = []      # (now, emb, guide, hg, hard)
        self._soft_clears: list[tuple] = []  # (now, index, ptr_snapshot)
        self._touches: list[tuple] = []      # (now, index, ptr_snapshot)
        self.epoch = 0                # bumped once per non-empty apply
        self.entries_applied = 0      # inserts ever applied (host counter)

    # -- staging --------------------------------------------------------
    def stage_add(self, emb, guide, has_guide: bool, hard: bool,
                  now: int) -> None:
        """Stage one ring insert (a shadow pass's recorded entry)."""
        self._records.append((int(now), emb, guide, bool(has_guide),
                              bool(hard)))

    def stage_soft_clear(self, index: int, now: int,
                         ptr_snapshot: int | None = None) -> None:
        """Stage a hard-flag clear after a successful re-probe.
        ``ptr_snapshot`` is the ring pointer when the target entry was
        observed (eviction guard; None = start of the applying epoch)."""
        self._soft_clears.append((int(now), int(index), ptr_snapshot))

    def stage_touch(self, index: int, now: int,
                    ptr_snapshot: int | None = None) -> None:
        """Stage a timestamp refresh (failed re-probe restarts the
        cool-down); ``ptr_snapshot`` as in :meth:`stage_soft_clear`."""
        self._touches.append((int(now), int(index), ptr_snapshot))

    # -- inspection -----------------------------------------------------
    @property
    def pending(self) -> int:
        return len(self._records) + len(self._soft_clears) + \
            len(self._touches)

    # -- partial-epoch rollback -----------------------------------------
    def mark(self) -> tuple:
        """Opaque cursor over the staging area, for :meth:`rollback`.
        Taken by a drain runner *before* it stages anything, so a
        mid-epoch failure can unstage exactly its own partial work and a
        queue-level retry replays from a clean slate (the
        lost-failed-epoch bugfix: re-queued items must not double-stage)."""
        return (len(self._records), len(self._soft_clears),
                len(self._touches))

    def rollback(self, mark: tuple) -> None:
        """Discard every op staged since ``mark``. Ops staged *before*
        the mark (another replica's epoch sharing this buffer) are
        untouched. If the buffer was applied since the mark (cursor now
        shorter than the mark), there is nothing of ours left to unstage
        — the clamp makes rollback after a racing apply a no-op rather
        than an error."""
        r, s, t = mark
        del self._records[min(r, len(self._records)):]
        del self._soft_clears[min(s, len(self._soft_clears)):]
        del self._touches[min(t, len(self._touches)):]

    # -- apply ----------------------------------------------------------
    def take_ops(self):
        """Drain the staged ops: returns ``(records, soft_clears,
        touches)`` (records sorted by logical ``now``) and leaves the
        staging area empty. Split from :meth:`apply_ops` so the commit
        stream can write the epoch to a write-ahead journal *between*
        taking and applying — the crash-consistency boundary."""
        records = sorted(self._records, key=lambda r: r[0])
        soft_clears, touches = self._soft_clears, self._touches
        self._records, self._soft_clears, self._touches = [], [], []
        return records, soft_clears, touches

    def apply(self, state):
        """Apply every staged op to ``state`` as one epoch; returns the
        (new) store and the number of entries inserted. Ops land in
        deterministic order (see class docstring); inserts are chunked at
        ring capacity so an epoch larger than the ring degrades to the
        sequential FIFO result instead of a self-overwriting scatter.
        Value semantics: ``state`` stays valid (the store is copied)."""
        if not self.pending:
            return state, 0
        return self.apply_ops(state, *self.take_ops())

    def apply_ops(self, state, records, soft_clears, touches, *,
                  ptr: int | None = None, donate: bool = False):
        """Apply one epoch's (already taken) ops to ``state``. This is
        the single code path both live drains and journal *recovery*
        replay go through — which is what makes a recovered store
        byte-identical to the pre-crash one.

        A :class:`MemoryState` takes the whole epoch as one program
        (:func:`_epoch_body`) per ``C`` inserts. ``donate`` is for a
        caller that owns the store and gives it up — the commit stream,
        the process fabric's worker mirror, journal replay: the program
        then updates the buffers in place and ``state`` must not be read
        again. ``ptr`` is the ring pointer as the caller's host mirror
        has it; None reads it from the device (one sync). Mutable stores
        (sharded, IVF) go through their own ``add_batch``/``mark_soft``/
        ``touch`` methods."""
        records = sorted(records, key=lambda r: r[0])
        C = state.capacity
        base_ptr = int(jax.device_get(state.ptr)) if ptr is None \
            else int(ptr)
        end_ptr = base_ptr + len(records)

        def evicted(idx: int, snap) -> bool:
            """Has slot ``idx`` been overwritten by any insert between
            the flag op's pointer snapshot and the end of this epoch's
            scatter? (Clamping guards against a snapshot from a mirror
            that missed out-of-band writes — over-covering only drops a
            flag update, never corrupts an entry.)"""
            snap = base_ptr if snap is None else min(int(snap), base_ptr)
            covered = end_ptr - snap
            return covered >= C or (idx - snap) % C < covered

        softs = sorted({idx for _, idx, snap in soft_clears
                        if not evicted(idx, snap)})
        # duplicate touch targets dedupe last-now-wins (scatter order for
        # duplicate indices is implementation-defined)
        by_idx = {idx: now for now, idx, snap in
                  sorted(touches, key=lambda t: t[:2])
                  if not evicted(idx, snap)}
        if isinstance(state, MemoryState):
            state = self._apply_epoch(state, records, softs, by_idx,
                                      donate)
        else:
            state = self._apply_each(state, records, softs, by_idx)
        self.epoch += 1
        self.entries_applied += len(records)
        return state, len(records)

    @staticmethod
    def _apply_epoch(state, records, softs, touches, donate: bool):
        """The fused epoch: one program per ``C`` inserts, the flag ops
        riding on the last (so an epoch of at most C inserts is a single
        call — all or nothing)."""
        if not (records or softs or touches):
            return state
        run = _epoch_donated_jit if donate else _epoch_jit
        C = state.capacity
        starts = range(0, len(records), C) if records else [0]
        for start in starts:
            last = start + C >= len(records)
            state = run(state, _epoch_operands(
                state, records[start:start + C],
                softs if last else [], touches if last else {}))
        return state

    @staticmethod
    def _apply_each(state, records, softs, by_idx):
        """A mutable store's epoch: its own scatters, inserts chunked at
        capacity, then the soft-clears, then the touches."""
        import numpy as np

        C = state.capacity

        def po2_chunks(seq):
            """Split into power-of-two-sized runs (13 -> 8+4+1): the
            jitted scatters compile one kernel per bucket size instead
            of one per arbitrary batch length, so a coalesced replay of
            many epochs can't trigger fresh compiles mid-serve. Order
            is preserved, so the scatter bytes are unchanged."""
            i = 0
            while i < len(seq):
                step = 1 << ((len(seq) - i).bit_length() - 1)
                yield seq[i:i + step]
                i += step

        for start in range(0, len(records), C):
            for chunk in po2_chunks(records[start:start + C]):
                state = add_batch(
                    state,
                    jnp.asarray(np.stack([np.asarray(r[1])
                                          for r in chunk])),
                    jnp.asarray(np.stack([np.asarray(r[2], np.int32)
                                          for r in chunk])),
                    jnp.asarray(np.asarray([r[3] for r in chunk], bool)),
                    jnp.asarray(np.asarray([r[4] for r in chunk], bool)),
                    jnp.asarray(np.asarray([r[0] for r in chunk],
                                           np.int32)))
        for chunk in po2_chunks(softs):
            state = mark_soft(state, jnp.asarray(chunk, jnp.int32))
        for chunk in po2_chunks(sorted(by_idx)):
            state = touch(state,
                          jnp.asarray(chunk, jnp.int32),
                          jnp.asarray([by_idx[i] for i in chunk],
                                      jnp.int32))
        return state


# ---------------------------------------------------------------------------
# Write-ahead journal — crash-consistent persistence of the commit stream
# ---------------------------------------------------------------------------


class JournalCorruptionWarning(UserWarning):
    """A WAL replay hit a torn or corrupt frame and stopped there.

    Carries where and why, so operators can distinguish the benign case
    (torn tail from a mid-write crash — expected, recovery is exact up
    to the previous epoch) from on-disk corruption earlier in the file
    (bit rot: every later epoch is lost)."""

    def __init__(self, path: str, offset: int, reason: str):
        super().__init__(f"WAL replay stopped at byte {offset} of "
                         f"{path}: {reason}")
        self.path = path
        self.offset = offset
        self.reason = reason


class MemoryJournal:
    """Epoch-granular write-ahead journal + periodic snapshot for one
    commit stream's store.

    Layout: ``<dir>/wal.log`` (append-only record stream) and
    ``<dir>/snapshot.npz`` (atomic store snapshot, written via
    :func:`repro.training.checkpoint.save_checkpoint`). Each WAL record
    is ``<u32 length><u32 crc32>`` + a pickled payload holding one
    epoch's taken ops (inserts as host arrays, flag clears, touches) and
    its epoch number.

    Protocol (see :meth:`CommitStream.apply`): the epoch's ops are
    journaled **and fsynced before** they are applied to the in-memory
    store. A crash before the WAL write loses the epoch entirely
    (recovery lands on the previous epoch — which is also all the crashed
    process's store ever showed); a crash after the WAL write but before
    the apply recovers *with* the epoch (one epoch ahead of the dead
    process's memory). Either way the recovered store equals a store
    some prefix of epochs was applied to — never a torn state.

    Every ``snapshot_every`` epochs the full store is snapshotted
    atomically (tmpfile + ``os.replace``) and the WAL is truncated;
    records carry their epoch number, so recovery filters anything the
    snapshot already covers — a crash *between* snapshot and truncation
    is harmless.

    :meth:`recover` replays surviving epochs through
    :meth:`CommitBuffer.apply_ops` — the very code path live drains use —
    so the restored store is byte-identical to the pre-crash commit
    state. A torn or corrupt WAL tail (short read / CRC mismatch) is
    tolerated: replay stops at the last complete record.

    Only the functional :class:`MemoryState` store is journalable (the
    sharded store mutates device buffers in place and has its own
    persistence story).
    """

    def __init__(self, path: str, *, snapshot_every: int = 8,
                 fault_plan=None):
        import os
        if snapshot_every < 1:
            raise ValueError(f"snapshot_every must be >= 1, "
                             f"got {snapshot_every}")
        os.makedirs(path, exist_ok=True)
        self.path = path
        self.wal_path = os.path.join(path, "wal.log")
        self.snap_path = os.path.join(path, "snapshot.npz")
        self.manifest_path = os.path.join(path, "manifest.pkl")
        self.snapshot_every = snapshot_every
        self.fault_plan = fault_plan
        self._wal = open(self.wal_path, "ab")
        self.epochs_logged = 0
        self.snapshots = 0

    # -- record framing -------------------------------------------------
    # one codec for WAL records and fabric RPC frames — the corruption
    # tests cover both at once
    @staticmethod
    def _frame(obj) -> bytes:
        from repro.serving.transport import frame_message
        return frame_message(obj)

    @staticmethod
    def _read_records(path):
        """Yield payload objects from a WAL file. Replay stops at the
        first torn or corrupt frame with a structured
        :class:`JournalCorruptionWarning` (never raises): everything
        before the bad frame is recovered, everything after is
        unreachable anyway — its epochs chain past the gap. A clean EOF
        stays silent."""
        import os
        import pickle
        import struct
        import warnings
        import zlib
        if not os.path.exists(path):
            return
        offset = 0
        with open(path, "rb") as f:
            while True:
                head = f.read(8)
                if len(head) == 0:
                    return                       # clean end
                if len(head) < 8:
                    warnings.warn(JournalCorruptionWarning(
                        path, offset,
                        f"torn header ({len(head)} of 8 bytes)"))
                    return
                length, crc = struct.unpack("<II", head)
                payload = f.read(length)
                if len(payload) < length:
                    warnings.warn(JournalCorruptionWarning(
                        path, offset, f"torn payload ({len(payload)} of "
                        f"{length} bytes)"))
                    return
                if zlib.crc32(payload) & 0xFFFFFFFF != crc:
                    warnings.warn(JournalCorruptionWarning(
                        path, offset, "crc mismatch"))
                    return
                yield pickle.loads(payload)
                offset += 8 + length

    # -- logging --------------------------------------------------------
    def log_epoch(self, epoch: int, records, soft_clears, touches,
                  manifest: dict | None = None) -> None:
        """Make one epoch's ops durable (write + flush + fsync). The
        ``wal_write`` fault site fires *before* the write — an injected
        crash here models dying with the epoch not yet on disk.

        ``manifest`` rides inside the same frame as the ops: one fsync
        makes the guide-store epoch *and* the engine-state snapshot it
        pairs with durable together, so recovery can never observe a
        store from epoch N with counters from epoch N±1."""
        import os

        import numpy as np
        if self.fault_plan is not None:
            self.fault_plan.fire("wal_write", epoch=epoch)
        host_records = [(now, np.asarray(emb), np.asarray(g, np.int32),
                         hg, hard) for now, emb, g, hg, hard in records]
        self._wal.write(self._frame({
            "epoch": int(epoch), "records": host_records,
            "soft_clears": list(soft_clears), "touches": list(touches),
            "manifest": manifest}))
        self._wal.flush()
        os.fsync(self._wal.fileno())
        self.epochs_logged += 1

    def log_checkpoint(self, epoch: int, manifest: dict) -> None:
        """Journal a manifest-only record: engine state *as of* the
        current epoch, with no store ops. Written at clean shutdown (and
        on demand) so state that advanced past the last store commit —
        the clock, counters of store-untouched requests — survives a
        subsequent kill. Replay takes the manifest, applies nothing."""
        import os
        self._wal.write(self._frame({
            "epoch": int(epoch), "checkpoint": True,
            "manifest": manifest}))
        self._wal.flush()
        os.fsync(self._wal.fileno())
        self.epochs_logged += 1

    def maybe_snapshot(self, state, buffer: CommitBuffer,
                       manifest: dict | None = None) -> None:
        if buffer.epoch % self.snapshot_every == 0:
            self.snapshot(state, buffer, manifest)

    def snapshot(self, state, buffer: CommitBuffer,
                 manifest: dict | None = None) -> None:
        """Atomically snapshot the full store + buffer counters, then
        truncate the WAL (safe in either order — see class docstring).
        The manifest lands in ``manifest.pkl`` (tmpfile + ``os.replace``)
        *before* the truncation: if we die between the two, the WAL's
        embedded manifests still cover every epoch past the snapshot."""
        import os
        import pickle

        import numpy as np
        from repro.training.checkpoint import save_checkpoint
        if manifest is not None:
            tmp = self.manifest_path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump({"epoch": int(buffer.epoch),
                             "manifest": manifest}, f, protocol=4)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.manifest_path)
        save_checkpoint(self.snap_path, {
            "state": state,
            "meta": np.asarray([buffer.epoch, buffer.entries_applied],
                               np.int64)})
        self._wal.close()
        self._wal = open(self.wal_path, "wb")   # truncate
        self._wal.flush()
        os.fsync(self._wal.fileno())
        self.snapshots += 1

    def close(self) -> None:
        if not self._wal.closed:
            self._wal.close()

    def stats(self) -> dict:
        return {"epochs_logged": self.epochs_logged,
                "snapshots": self.snapshots}

    # -- recovery -------------------------------------------------------
    @staticmethod
    def recover(path: str, mem_cfg: MemoryConfig):
        """Rebuild the store from ``<path>`` after a crash.

        Returns ``(state, epoch, entries_applied, manifest)`` — the
        recovered :class:`MemoryState`, the buffer counters a resumed
        stream must continue from, and the newest engine-state manifest
        that is *consistent with the recovered store* (``None`` when the
        site never journaled one) — or ``None`` if the directory holds
        neither snapshot nor WAL (a fresh site). Replays every complete
        WAL record newer than the snapshot through
        :meth:`CommitBuffer.apply_ops`, in epoch (= file) order; each
        replayed record's embedded manifest supersedes the snapshot-side
        one, so store and manifest always come from the same fsync.
        """
        import os
        import pickle

        import numpy as np
        from repro.training.checkpoint import load_checkpoint
        snap_path = os.path.join(path, "snapshot.npz")
        wal_path = os.path.join(path, "wal.log")
        man_path = os.path.join(path, "manifest.pkl")
        have_snap = os.path.exists(snap_path)
        have_wal = os.path.exists(wal_path) and \
            os.path.getsize(wal_path) > 0
        if not have_snap and not have_wal:
            return None
        manifest = None
        if have_snap:
            tree = load_checkpoint(snap_path)
            state = jax.tree.map(jnp.asarray, tree["state"])
            epoch, entries = (int(x) for x in np.asarray(tree["meta"]))
            if os.path.exists(man_path):
                with open(man_path, "rb") as f:
                    manifest = pickle.load(f)["manifest"]
        else:
            state, epoch, entries = init_memory(mem_cfg), 0, 0
        replay = CommitBuffer()
        replay.epoch, replay.entries_applied = epoch, entries
        for rec in MemoryJournal._read_records(wal_path):
            if rec.get("checkpoint"):
                if rec["epoch"] >= replay.epoch:
                    manifest = rec["manifest"]
                continue                      # manifest only, no ops
            if rec["epoch"] <= epoch:
                continue                      # snapshot already covers it
            # the replay owns its store: each epoch updates it in place
            state, _ = replay.apply_ops(state, rec["records"],
                                        rec["soft_clears"],
                                        rec["touches"], donate=True)
            replay.epoch = rec["epoch"]       # keep numbering exact
            if rec.get("manifest") is not None:
                manifest = rec["manifest"]
        return state, replay.epoch, replay.entries_applied, manifest


# ---------------------------------------------------------------------------
# Commit stream — the serve/learn interface around the commit buffer
# ---------------------------------------------------------------------------


class CommitStream:
    """The serve/learn commit interface of one serving site.

    Generalizes what used to be three per-controller pieces — the shadow
    queue's ``store_lock``, its :class:`CommitBuffer`, and the
    controller's private host-side commit counter — into one object that
    any number of serve replicas can share:

    * :attr:`buffer` — the epoch-versioned staging area for all learn-
      plane writes (one per stream: every replica's shadow drain stages
      into the same epochs);
    * :attr:`lock` — serializes commit applies against serve-plane
      snapshot reads (for the functional ``MemoryState`` the apply is a
      reference swap; for the mutable sharded store the lock is what
      makes the multi-field update atomic for readers);
    * :attr:`commits` — the **single** host-side counter of entries ever
      committed, owned here rather than per-controller so
      ``RAR.memory_occupancy`` stays exact when N replicas share a store
      (each replica previously counted only its own writes);
    * subscribed **views** — controllers whose ``.memory`` mirrors the
      store: every applied epoch is broadcast to all of them under the
      lock, so replicas always read a whole number of epochs.

    A standalone controller owns a private stream with itself as the only
    view; the serving fabric (:mod:`repro.serving.fabric`) passes one
    shared stream to all its replicas.

    **The stream owns the store it applies to.** :meth:`apply` donates a
    :class:`MemoryState` to the epoch program, which updates its buffers
    in place, and hands the new store to every view: a store passed to
    :meth:`apply` (or :meth:`apply_ops`) must not be read again, and
    readers take the store from their view under :attr:`lock`, never
    from a reference kept across an apply. The stream keeps a host
    mirror of the ring pointer (:attr:`ptr_host`), so an epoch reads no
    pointer from the device.

    With a :class:`MemoryJournal` attached the stream is
    crash-consistent: each epoch's ops are journaled (write-ahead,
    fsynced) before the in-memory apply, and the store is periodically
    snapshotted — see :meth:`MemoryJournal.recover` /
    :func:`open_journaled_stream`. A :class:`repro.serving.faults`
    fault plan fires at the ``wal_write`` / ``commit_apply`` boundary so
    the crash-consistency property is testable deterministically.
    """

    def __init__(self, buffer: CommitBuffer | None = None, *,
                 journal: "MemoryJournal | None" = None, fault_plan=None):
        self.buffer = buffer if buffer is not None else CommitBuffer()
        self.lock = threading.RLock()
        self.commits = 0             # entries ever committed (host-side)
        self._views: list = []       # controllers mirroring the store
        # host mirror of the ring pointer of the store this stream last
        # produced (``_store``): read from the device when the stream
        # first applies (or is handed a store it did not produce),
        # advanced by each epoch's inserts, re-read by ``grow``
        self.ptr_host: int | None = None
        self._store = None
        self.journal = journal
        self.fault_plan = fault_plan
        # engine-state exporter (set by the owning controller/fabric):
        # called under the stream lock right before an epoch is
        # journaled, its dict rides in the same WAL frame as the ops —
        # the epoch-consistent recovery manifest
        self.state_provider = None
        # per-epoch ops tap (set by the process fabric): called under
        # the lock after a successful apply with the epoch's taken ops,
        # so the fabric can broadcast them to out-of-process workers
        self.ops_listener = None
        # optional metrics registry (set by the owning fabric): applied
        # epochs/entries counters + current-epoch gauge, bumped under
        # the stream lock — all host ints, zero device syncs
        self.metrics = None

    def subscribe(self, view) -> None:
        """Register a controller whose ``.memory`` tracks this stream's
        store (idempotent). ``view.commit_epoch_seen`` tracks the last
        epoch broadcast to it — the per-view commit-lag metric."""
        if view not in self._views:
            self._views.append(view)
            view.commit_epoch_seen = self.buffer.epoch

    def count(self, n: int = 1) -> None:
        """Account ``n`` direct (non-buffered) commits — the sequential
        controller's per-request writes."""
        with self.lock:
            self.commits += n

    def apply(self, state):
        """Apply the staged epoch to ``state`` and broadcast the new
        store to every subscribed view atomically (one lock hold covers
        the apply, the counter bump and all view updates). With a
        journal, the epoch is made durable (write-ahead) before the
        apply; the ``commit_apply`` fault site fires between the two —
        the kill-mid-epoch point the recovery property tests. Returns
        the new store.

        ``state`` must be the store the views hold; it is given up (see
        the class docstring). A non-empty epoch is one ``rar.commit``
        span; its seconds go to the ``commit/apply_seconds`` histogram.
        Only the pointer mirror's first read counts as a
        ``host/syncs/commit`` fetch."""
        with self.lock:
            if not self.buffer.pending:
                return state
            t0 = time.monotonic()
            with span("rar.commit"):
                state = self._apply_locked(state)
            if self.metrics is not None:
                self.metrics.histogram("commit/apply_seconds").observe(
                    time.monotonic() - t0)
        return state

    def _apply_locked(self, state):
        records, soft_clears, touches = self.buffer.take_ops()
        epoch = self.buffer.epoch + 1
        manifest = None
        if self.journal is not None:
            if self.state_provider is not None:
                manifest = self.state_provider()
            self.journal.log_epoch(epoch, records, soft_clears, touches,
                                   manifest)
        if self.fault_plan is not None:
            self.fault_plan.fire("commit_apply", epoch=epoch)
        in_place = isinstance(state, MemoryState)
        state, n = self.apply_ops(state, records, soft_clears, touches)
        self.commits += n
        for v in self._views:
            v.memory = state
            v.commit_epoch_seen = self.buffer.epoch
        if self.ops_listener is not None:
            self.ops_listener(epoch, records, soft_clears, touches, n)
        if self.metrics is not None:
            with self.metrics.lock:
                self.metrics.counter("commit/epochs_applied").inc()
                if in_place:
                    self.metrics.counter("commit/epochs_in_place").inc()
                self.metrics.counter("commit/entries_applied").inc(n)
                self.metrics.gauge("commit/epoch").set(self.buffer.epoch)
        if self.journal is not None:
            self.journal.maybe_snapshot(state, self.buffer, manifest)
        return state

    def apply_ops(self, state, records, soft_clears, touches):
        """Apply one epoch's taken ops to the stream's own store, in
        place: :meth:`CommitBuffer.apply_ops` with the host pointer
        mirror and the store donated. ``state`` must not be read again.
        Returns ``(new_state, inserted)``; broadcasts nothing (the
        process fabric's worker mirror calls it directly)."""
        with self.lock:
            if self.ptr_host is None or state is not self._store:
                if isinstance(state.ptr, jax.Array):
                    count_syncs(self.metrics, "commit")
                self.ptr_host = int(jax.device_get(state.ptr))
            state, n = self.buffer.apply_ops(state, records, soft_clears,
                                             touches, ptr=self.ptr_host,
                                             donate=True)
            self.ptr_host += n
            self._store = state
            return state, n

    def grow(self, state, new_capacity: int):
        """Grow the stream's store in place (capacity re-layout) and
        re-broadcast it to every subscribed view atomically. Refuses
        while commit ops are staged — a wrapped-ring grow moves slot
        indices, so staged flag ops (which carry old indices) must drain
        first; see :func:`grow_memory`. Each view's ``_ptr_base`` is
        rebased to the grown pointer so the serve path's host-side
        ``ptr_snapshot`` arithmetic (``_ptr_base + commits``) stays exact
        across the grow. Returns ``(new_state, remap)``."""
        with self.lock:
            if self.buffer.pending:
                raise RuntimeError(
                    f"grow with {self.buffer.pending} staged commit ops; "
                    f"drain (apply) the epoch first")
            if isinstance(state, MemoryState):
                state, remap = grow_memory(state, new_capacity)
            else:
                state, remap = state.grow(new_capacity)
            new_ptr = int(jax.device_get(state.ptr))
            self.ptr_host, self._store = new_ptr, state
            for v in self._views:
                v.memory = state
                if hasattr(v, "_ptr_base"):
                    v._ptr_base = new_ptr - self.commits
            return state, remap

    def checkpoint(self) -> None:
        """Journal a manifest-only record at the current epoch — called
        at clean shutdown (and by tests) so engine state that advanced
        past the last store commit survives a later kill. No-op without
        a journal or a state provider."""
        with self.lock:
            if self.journal is None or self.state_provider is None:
                return
            self.journal.log_checkpoint(self.buffer.epoch,
                                        self.state_provider())

    def commit_direct(self, state, *, record=None, soft_clear=None,
                      touch_op=None):
        """Commit the sequential controller's per-request write as one
        single-op epoch through the staged path (so it hits the journal
        like any drain epoch). ``record`` is a ``stage_add`` tuple
        ``(emb, guide, has_guide, hard, now)``; ``soft_clear`` /
        ``touch_op`` are ``(index, now, ptr_snapshot)``. Returns the new
        store. Byte-identical to the direct ``add``/``mark_soft``/
        ``touch`` calls it replaces (a K=1 ``add_batch`` is the pinned
        equivalent of ``add``) — the sequential controller only routes
        through here when a journal is attached."""
        with self.lock:
            if record is not None:
                emb, guide, has_guide, hard, now = record
                self.buffer.stage_add(emb, guide, has_guide, hard, now)
            if soft_clear is not None:
                self.buffer.stage_soft_clear(*soft_clear)
            if touch_op is not None:
                self.buffer.stage_touch(*touch_op)
            return self.apply(state)


def open_journaled_stream(path: str, mem_cfg: MemoryConfig, *,
                          snapshot_every: int = 8, fault_plan=None):
    """Open (or re-open after a crash) a journaled commit stream at
    ``path``. Returns ``(stream, recovered_state, manifest)`` —
    ``recovered_state`` is the byte-identical pre-crash store and
    ``manifest`` the engine-state dict journaled with its last epoch
    (both ``None`` for a fresh site). The stream's buffer counters
    resume from the recovered epoch, so WAL epoch numbering stays
    monotone across restarts."""
    recovered = MemoryJournal.recover(path, mem_cfg)
    journal = MemoryJournal(path, snapshot_every=snapshot_every,
                            fault_plan=fault_plan)
    stream = CommitStream(journal=journal, fault_plan=fault_plan)
    state, manifest = None, None
    if recovered is not None:
        state, epoch, entries, manifest = recovered
        stream.buffer.epoch = epoch
        stream.buffer.entries_applied = entries
        stream.ptr_host = int(jax.device_get(state.ptr))
        stream._store = state
    return stream, state, manifest


# ---------------------------------------------------------------------------
# Public API — thin dispatchers so the controllers (``core.rar`` /
# ``core.pipeline``) serve identically against the single-device
# MemoryState (functional, jitted) or a ``core.memory_sharded``
# ShardedMemory (method-based, returns itself after in-place update).
# ---------------------------------------------------------------------------


def query(state, emb: jax.Array, guides_only: bool = False) -> QueryResult:
    """Top-1 cosine search. ``guides_only`` restricts to guide entries
    (the guide-memory view used during shadow inference) via the mask bit
    plane — same single store pass, no mask combine. Kernel + metadata
    epilogue are one jitted call returning one packed struct."""
    if isinstance(state, MemoryState):
        return _query_jit(state, emb, guides_only=guides_only)
    return state.query(emb, guides_only=guides_only)


def query_batch(state, embs: jax.Array,
                guides_only: bool = False) -> QueryResult:
    """Top-1 cosine search for a whole microbatch of queries in one store
    pass. embs (B, E) → QueryResult with leading B axis. All queries see
    the same snapshot of the store (reads happen at microbatch start;
    writes commit at microbatch end via :func:`add_batch`)."""
    if isinstance(state, MemoryState):
        return _query_batch_jit(state, embs, guides_only=guides_only)
    return state.query_batch(embs, guides_only=guides_only)


def _check_k(k: int, capacity: int) -> None:
    # the upper bound holds on every backend: the Pallas kernel's (k, B)
    # accumulator must fit one grid-step merge (k <= kernel block), and
    # capping here also bounds the ref oracle's k unrolled selection
    # rounds — the dispatch contract cannot depend on which impl runs
    bound = min(capacity, DEFAULT_BLOCK_C)
    if not 1 <= k <= bound:
        raise ValueError(f"retrieval k={k} must be in [1, {bound}] "
                         f"(min of capacity={capacity} and the kernel "
                         f"block {DEFAULT_BLOCK_C})")


def query_topk(state, emb: jax.Array, k: int,
               guides_only: bool = False) -> TopKResult:
    """Top-k cosine search in the same single store pass as :func:`query`
    (k = 1 is bit-identical to it). Entries arrive sorted by
    (sim desc, store row asc); slots past the view's population carry the
    -2.0 sentinel. The multi-guide serving read
    (``core.rar.splice_guides``)."""
    _check_k(k, state.capacity)
    if isinstance(state, MemoryState):
        return _query_topk_jit(state, emb, k, guides_only=guides_only)
    return state.query_topk(emb, k, guides_only=guides_only)


def query_topk_batch(state, embs: jax.Array, k: int,
                     guides_only: bool = False) -> TopKResult:
    """Top-k search for a whole microbatch in one store pass: embs (B, E)
    → TopKResult with (B, k) leading axes. Snapshot semantics match
    :func:`query_batch`."""
    _check_k(k, state.capacity)
    if isinstance(state, MemoryState):
        return _query_topk_batch_jit(state, embs, k,
                                     guides_only=guides_only)
    return state.query_topk_batch(embs, k, guides_only=guides_only)


def add(state, emb: jax.Array, guide: jax.Array, has_guide: jax.Array,
        hard: jax.Array, now: jax.Array):
    """Insert one entry at the ring pointer (FIFO eviction). Scatters one
    padded row; ``state`` stays valid, so a :class:`MemoryState` is copied
    first. Only the commit stream's epoch (:meth:`CommitStream.apply`),
    which gives its store up, never re-materializes the store."""
    if isinstance(state, MemoryState):
        return _add_jit(state, emb, guide, has_guide, hard, now)
    state.add(emb, guide, has_guide, hard, now)
    return state


def add_batch(state, embs: jax.Array, guides: jax.Array,
              has_guide: jax.Array, hard: jax.Array, now: jax.Array):
    """Insert K entries at consecutive ring slots in one jitted call — the
    microbatch commit (all of a batch's shadow-inference writes land
    together). embs (K, E); guides (K, G); has_guide/hard (K,) bool;
    now (K,) int32 per-entry logical times. Equivalent to K sequential
    :func:`add` calls for K ≤ capacity (slot indices are then distinct, so
    the scatter order cannot matter)."""
    if isinstance(state, MemoryState):
        return _add_batch_jit(state, embs, guides, has_guide, hard, now)
    state.add_batch(embs, guides, has_guide, hard, now)
    return state


def mark_soft(state, index: jax.Array):
    """Clear a hard flag after a successful re-probe (Case 3 → Case 1/2).
    ``index`` may be a scalar or a (K,) batch of indices (the microbatch
    commit's flag pass)."""
    if isinstance(state, MemoryState):
        return _mark_soft_jit(state, index)
    state.mark_soft(index)
    return state


def touch(state, index: jax.Array, now: jax.Array):
    """Refresh an entry's timestamp (restarts the re-probe cool-down).
    ``index``/``now`` may be scalars or matching (K,) batches."""
    if isinstance(state, MemoryState):
        return _touch_jit(state, index, now)
    state.touch(index, now)
    return state
