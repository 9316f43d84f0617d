"""Two-level (IVF) retrieval plane: hierarchical recall vs. the exact
oracle, route-kernel parity, byte-identity of the IVF-off default,
per-shard route merge, grow-in-place, and host-offload tiering.

The load-bearing invariants:

* probing **all** clusters reproduces the exhaustive scan's valid
  entries exactly (same total order end to end), for any write history
  including ring wrap — the exactness anchor the recall property
  degrades from;
* ``retrieval_clusters = 0`` (the default) constructs no wrapper at all
  — controllers and fabric serve bit-identically to the pre-IVF stack;
* per-shard centroid-subset routes merge bit-identically into the
  global route (THE shared (score desc, row asc) total order).
"""
import dataclasses

import numpy as np
import pytest
from _hyp import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import memory as mem
from repro.core.memory_ivf import IVFMemory, _route_merged, wrap_store
from repro.kernels import ref
from repro.kernels.memory_ivf import ivf_route_batch_padded_pallas, \
    ivf_route_padded_pallas
from repro.kernels.memory_topk import MASK_GUIDE, MASK_VALID

E, G = 32, 8


def _protos(rng, n, e=E):
    p = rng.normal(size=(n, e)).astype(np.float32)
    return p / np.linalg.norm(p, axis=1, keepdims=True)


def _clustered(rng, protos, n, noise=0.05):
    x = protos[rng.integers(0, len(protos), n)] \
        + noise * rng.normal(size=(n, protos.shape[1])).astype(np.float32)
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _fill(store, rng, X, guide_frac=0.7, chunk=32):
    for i in range(0, len(X), chunk):
        xb = X[i:i + chunk]
        k = len(xb)
        store.add_batch(
            jnp.asarray(xb), jnp.asarray(rng.integers(
                0, 100, size=(k, G)).astype(np.int32)),
            jnp.asarray(rng.random(k) < guide_frac),
            jnp.asarray(rng.random(k) < 0.5),
            jnp.asarray(np.full(k, i, np.int32)))


def _assert_matches_exact(ivf, q_or_qs, k, guides_only=False, batch=False):
    """IVF result equals the exact oracle on every valid entry (valid
    rows agree bitwise on index/meta, sims to float tolerance; sentinel
    entries agree on the -2.0 sim — their index is implementation-
    defined on both sides)."""
    if batch:
        got = ivf.query_topk_batch(q_or_qs, k, guides_only=guides_only)
        want = ivf.exact_query_topk_batch(q_or_qs, k,
                                          guides_only=guides_only)
    else:
        got = ivf.query_topk(q_or_qs, k, guides_only=guides_only)
        want = ivf.exact_query_topk(q_or_qs, k, guides_only=guides_only)
    gs, ws = np.asarray(got.sim), np.asarray(want.sim)
    np.testing.assert_allclose(gs, ws, atol=1e-5)
    valid = ws > -2.0
    np.testing.assert_array_equal(np.asarray(got.index)[valid],
                                  np.asarray(want.index)[valid])
    np.testing.assert_array_equal(np.asarray(got.meta)[valid],
                                  np.asarray(want.meta)[valid])


# ---------------------------------------------------------------------------
# Route kernel: pallas (interpret) vs jnp oracle
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([9, 16, 33, 100]),     # P (odd → padding)
       st.sampled_from([1, 2, 4, 8]),         # n_probe
       st.sampled_from([1, 3, 16]),           # B
       st.sampled_from([0.0, 0.5, 1.0]))      # seeded density
def test_route_kernel_matches_oracle(seed, P, n_probe, B, density):
    rng = np.random.default_rng(seed)
    from repro.kernels.memory_topk import to_padded_layout
    cent = _protos(rng, P)
    bits = (rng.random(P) < density).astype(np.int32) * MASK_VALID
    centp, cmaskp = to_padded_layout(jnp.asarray(cent), jnp.asarray(bits),
                                     block_c=64)
    qs = jnp.asarray(_protos(rng, B))
    s_o, i_o = ref.ivf_route_batch_padded(centp, qs, cmaskp, n_probe)
    s_p, i_p = ivf_route_batch_padded_pallas(centp, qs, cmaskp,
                                             n_probe=n_probe, block_p=64,
                                             interpret=True)
    np.testing.assert_array_equal(np.asarray(i_o), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(s_o), np.asarray(s_p), atol=1e-6)
    s1_o, i1_o = ref.ivf_route_padded(centp, qs[0], cmaskp, n_probe)
    s1_p, i1_p = ivf_route_padded_pallas(centp, qs[0], cmaskp,
                                         n_probe=n_probe, block_p=64,
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(i1_o), np.asarray(i1_p))
    np.testing.assert_allclose(np.asarray(s1_o), np.asarray(s1_p),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Hierarchical recall@k property suite vs. the exact-scan oracle
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([64, 100, 256]),       # C
       st.sampled_from([1, 2, 4]),            # k
       st.sampled_from([4, 8, 16]),           # clusters
       st.sampled_from([0.3, 0.8, 1.2]),      # fill fraction (>1 → wrap)
       st.booleans())                         # guides-only view
def test_property_all_probes_equals_exact(seed, C, k, clusters, fill,
                                          guides_only):
    """The exactness anchor: probes == clusters makes the two-level read
    reproduce the exhaustive scan on every valid entry — for partial
    fills, duplicate embeddings (tie-break), guides-only views, and
    ring-wrapped histories with stale member entries."""
    rng = np.random.default_rng(seed)
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    ivf = IVFMemory(store, clusters=clusters, probes=clusters)
    protos = _protos(rng, clusters)
    n = int(C * fill)
    if n:
        X = _clustered(rng, protos, n)
        if n >= 3:
            X[n // 2] = X[0]               # duplicate row → tied sims
        _fill(ivf, rng, X)
    qs = jnp.asarray(_clustered(rng, protos, 5))
    _assert_matches_exact(ivf, qs[0], k, guides_only=guides_only)
    _assert_matches_exact(ivf, qs, k, guides_only=guides_only, batch=True)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([4, 8]),               # probes < clusters
       st.sampled_from([1, 4]))               # k
def test_property_recall_on_clustered_data(seed, probes, k):
    """The recall@k knob on skill-structured data (the workload the
    plane serves — same-skill cosine ≈ 0.99): at probes ≥ 4 of 16
    clusters, recall against the exact oracle stays ≥ 0.9."""
    rng = np.random.default_rng(seed)
    C, clusters = 512, 16
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    ivf = IVFMemory(store, clusters=clusters, probes=probes)
    protos = _protos(rng, clusters)
    _fill(ivf, rng, _clustered(rng, protos, C), guide_frac=1.0)
    qr = jnp.asarray(_clustered(rng, protos, 32))
    got = np.asarray(ivf.query_topk_batch(qr, k).index)
    want = np.asarray(ivf.exact_query_topk_batch(qr, k).index)
    recall = np.mean([len(set(got[b]) & set(want[b])) / k
                      for b in range(len(qr))])
    assert recall >= 0.9, recall


# ---------------------------------------------------------------------------
# IVF-off byte-identity: the default constructs no wrapper at all
# ---------------------------------------------------------------------------


def test_default_config_wraps_nothing():
    from repro.core.rar import RARConfig
    cfg = RARConfig()
    assert cfg.retrieval_clusters == 0
    store = mem.init_memory(cfg.memory)
    assert wrap_store(store, cfg) is store


def test_ivf_off_query_path_bit_identical(rng):
    """With retrieval off the serve path runs the exact same dispatch as
    before this module existed: query results on an untouched store are
    bitwise equal whether or not the IVF module is imported/configured
    (the wrapper is never constructed — same object, same bytes)."""
    store = mem.init_memory(mem.MemoryConfig(capacity=64, embed_dim=E,
                                             guide_len=G))
    X = _clustered(rng, _protos(rng, 4), 40)
    store = mem.add_batch(store, jnp.asarray(X),
                          jnp.zeros((40, G), jnp.int32),
                          jnp.ones(40, bool), jnp.zeros(40, bool),
                          jnp.zeros(40, jnp.int32))
    from repro.core.rar import RARConfig
    wrapped = wrap_store(store, RARConfig())
    assert wrapped is store
    q = jnp.asarray(X[3])
    a = mem.query_topk(store, q, 4)
    b = mem.query_topk(wrapped, q, 4)
    np.testing.assert_array_equal(np.asarray(a.sim), np.asarray(b.sim))
    np.testing.assert_array_equal(np.asarray(a.meta), np.asarray(b.meta))
    ab = mem.query_topk_batch(store, jnp.asarray(X[:8]), 4)
    bb = mem.query_topk_batch(wrapped, jnp.asarray(X[:8]), 4)
    np.testing.assert_array_equal(np.asarray(ab.sim), np.asarray(bb.sim))
    np.testing.assert_array_equal(np.asarray(ab.meta), np.asarray(bb.meta))


def test_controller_default_keeps_raw_store():
    from repro.core.rar import RAR, RARConfig
    cfg = RARConfig(memory=mem.MemoryConfig(capacity=32, embed_dim=E,
                                            guide_len=G))
    rar = RAR(None, None, lambda p: None, lambda e, k: False, cfg)
    assert isinstance(rar.memory, mem.MemoryState)
    on = dataclasses.replace(cfg, retrieval_clusters=4, retrieval_probes=2)
    rar2 = RAR(None, None, lambda p: None, lambda e, k: False, on)
    assert isinstance(rar2.memory, IVFMemory)
    # idempotent: injecting an already-wrapped store wraps nothing new
    rar3 = RAR(None, None, lambda p: None, lambda e, k: False, on,
               memory=rar2.memory)
    assert rar3.memory is rar2.memory


# ---------------------------------------------------------------------------
# Sharded composition: per-shard centroid subsets merge bit-identically
# ---------------------------------------------------------------------------


def test_per_shard_route_merge_bit_identical(rng):
    """Cluster → shard placement: routing S per-shard centroid subsets
    and merging under the shared total order is bit-identical to routing
    the one global centroid plane (all clusters seeded — unseeded rows
    surface sentinels whose ids are implementation-defined)."""
    from repro.kernels.memory_topk import to_padded_layout
    P, S, n_probe = 16, 4, 4
    cent = _protos(rng, P)
    bits = np.full(P, MASK_VALID, np.int32)

    def plane(ids):
        cp, mp = to_padded_layout(jnp.asarray(cent[ids]),
                                  jnp.asarray(bits[ids]), block_c=64)
        return (cp, mp, jnp.asarray(ids.astype(np.int32)))

    global_plane = [plane(np.arange(P))]
    shard_planes = [plane(np.flatnonzero(np.arange(P) % S == s))
                    for s in range(S)]
    for trial in range(10):
        q = jnp.asarray(_protos(rng, 1)[0])
        sg, ig = jax.jit(
            lambda pl, q: _route_merged(pl, q, n_probe))(global_plane, q)
        ss, is_ = jax.jit(
            lambda pl, q: _route_merged(pl, q, n_probe))(shard_planes, q)
        np.testing.assert_array_equal(np.asarray(ig), np.asarray(is_))
        np.testing.assert_array_equal(np.asarray(sg), np.asarray(ss))


def test_sharded_backing_matches_exact(rng):
    """IVF over a ShardedMemory backing (single host device — the
    degenerate 1-shard mesh): all-probe reads equal the exact oracle."""
    from repro.core.memory_sharded import ShardedMemory
    C = 128
    sh = ShardedMemory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                        guide_len=G))
    ivf = IVFMemory(sh, clusters=8, probes=8)
    protos = _protos(rng, 8)
    _fill(ivf, rng, _clustered(rng, protos, C + 40))   # wraps the ring
    qs = jnp.asarray(_clustered(rng, protos, 6))
    _assert_matches_exact(ivf, qs[0], 4)
    _assert_matches_exact(ivf, qs, 4, batch=True)


# ---------------------------------------------------------------------------
# Grow-in-place capacity re-layout
# ---------------------------------------------------------------------------


def _store_with(rng, C, n):
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    X = _clustered(rng, _protos(rng, 4), n)
    for i in range(0, n, 16):
        xb = X[i:i + 16]
        store = mem.add_batch(
            store, jnp.asarray(xb),
            jnp.asarray(rng.integers(0, 50, size=(len(xb), G)).astype(
                np.int32)),
            jnp.ones(len(xb), bool), jnp.zeros(len(xb), bool),
            jnp.asarray(np.arange(i, i + len(xb)), np.int32))
    return store, X


def test_grow_unwrapped_preserves_slots_and_ptr(rng):
    store, X = _store_with(rng, 64, 40)              # ptr 40 <= C
    grown, remap = mem.grow_memory(store, 128)
    assert grown.capacity == 128
    assert int(grown.ptr) == 40
    np.testing.assert_array_equal(np.asarray(remap), np.arange(64))
    # occupied entries land on the SAME slots, bitwise
    np.testing.assert_array_equal(np.asarray(store.emb)[:40],
                                  np.asarray(grown.emb)[:40])
    np.testing.assert_array_equal(np.asarray(store.guide)[:40],
                                  np.asarray(grown.guide)[:40])
    np.testing.assert_array_equal(np.asarray(store.mask)[:40, 0],
                                  np.asarray(grown.mask)[:40, 0])
    assert not np.asarray(grown.valid)[40:].any()


def test_grow_wrapped_linearizes_oldest_first(rng):
    C = 64
    store, X = _store_with(rng, C, 100)              # ptr 100 > C: wrapped
    grown, remap = mem.grow_memory(store, 128)
    assert int(grown.ptr) == C                       # linearized: oldest=0
    old_emb = np.asarray(store.emb)
    new_emb = np.asarray(grown.emb)
    old_t = np.asarray(store.added_at)
    new_t = np.asarray(grown.added_at)
    r = np.asarray(remap)
    for s in range(C):                               # entry follows remap
        np.testing.assert_array_equal(old_emb[s], new_emb[r[s]])
        assert old_t[s] == new_t[r[s]]
    assert (np.diff(new_t[:C]) >= 0).all()           # oldest-first order
    # growing again (now unwrapped) keeps continuing writes exact
    again, remap2 = mem.grow_memory(grown, 256)
    np.testing.assert_array_equal(np.asarray(remap2), np.arange(128))


def test_grow_smaller_rejected(rng):
    store, _ = _store_with(rng, 64, 10)
    with pytest.raises(ValueError):
        mem.grow_memory(store, 32)


def test_commit_stream_grow_rebases_and_refuses_pending(rng):
    class View:
        pass

    store, _ = _store_with(rng, 64, 40)
    stream = mem.CommitStream()
    v = View()
    v.memory = store
    v._ptr_base = 40
    stream.subscribe(v)
    # staged-but-undrained ops must block the re-layout
    stream.buffer.stage_add(np.zeros(E, np.float32),
                            np.zeros(G, np.int32), True, False, 0)
    with pytest.raises(RuntimeError):
        stream.grow(store, 128)
    stream.buffer.take_ops()                         # drain the epoch
    grown, remap = stream.grow(store, 128)
    assert v.memory is grown
    assert v._ptr_base == 40 - stream.commits
    # post-grow eviction guards: a snapshot taken now covers exactly the
    # inserts that follow it
    buf = mem.CommitBuffer()
    snap = int(grown.ptr)
    state2 = grown
    for j in range(3):
        buf.stage_add(np.zeros(E, np.float32),
                      np.zeros(G, np.int32), True, False, j)
    buf.stage_soft_clear(5, 9, ptr_snapshot=snap)    # slot 5 < 40: safe
    buf.stage_soft_clear(41, 9, ptr_snapshot=snap)   # slot 41: evicted
    state2, n = buf.apply(state2)
    assert n == 3


def test_ivf_grow_requeries_exact(rng):
    C = 64
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    ivf = IVFMemory(store, clusters=8, probes=8)
    protos = _protos(rng, 8)
    _fill(ivf, rng, _clustered(rng, protos, C + 24))  # wrapped ring
    ivf2, remap = ivf.grow(2 * C)
    assert ivf2 is ivf and ivf.capacity == 2 * C
    _fill(ivf, rng, _clustered(rng, protos, 32))      # grow-in-place: keep
    qs = jnp.asarray(_clustered(rng, protos, 4))
    _assert_matches_exact(ivf, qs[0], 4)
    _assert_matches_exact(ivf, qs, 4, batch=True)


def test_ivf_grow_widens_default_buckets():
    """A default bucket width follows the grown capacity: with the width
    of the old ring, this seed's skewed cluster overflowed its bucket
    after the grow and probing every cluster missed rows."""
    rng = np.random.default_rng(56)
    C = 64
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    ivf = IVFMemory(store, clusters=8, probes=8)
    protos = _protos(rng, 8)
    _fill(ivf, rng, _clustered(rng, protos, C + 24))
    cap = ivf.bucket_cap
    ivf.grow(2 * C)
    assert ivf.bucket_cap == 2 * cap
    _fill(ivf, rng, _clustered(rng, protos, 32))
    assert ivf.stats()["bucket_evictions"] == 0
    qs = jnp.asarray(_clustered(rng, protos, 4))
    _assert_matches_exact(ivf, qs, 4, batch=True)


# ---------------------------------------------------------------------------
# Host-offload tiering
# ---------------------------------------------------------------------------


def test_offload_parity_and_traffic_split(rng):
    C, P = 128, 8
    store = mem.init_memory(mem.MemoryConfig(capacity=C, embed_dim=E,
                                             guide_len=G))
    hot = IVFMemory(store, clusters=P, probes=1)
    cold = IVFMemory(store, clusters=P, probes=1, offload=True,
                     cold_after=4)
    protos = _protos(rng, P)
    X = _clustered(rng, protos, C)
    _fill(hot, np.random.default_rng(7), X)          # identical metadata
    _fill(cold, np.random.default_rng(7), X)
    qa = jnp.asarray(_clustered(rng, protos[:1], 1)[0])
    for _ in range(10):                 # cluster 0 stays hot, rest cool
        a, b = hot.query_topk(qa, 3), cold.query_topk(qa, 3)
        np.testing.assert_array_equal(np.asarray(a.sim), np.asarray(b.sim))
    qb = jnp.asarray(_clustered(rng, protos[5:6], 1)[0])
    a, b = hot.query_topk(qb, 3), cold.query_topk(qb, 3)
    np.testing.assert_array_equal(np.asarray(a.sim), np.asarray(b.sim))
    valid = np.asarray(a.sim) > -2.0
    np.testing.assert_array_equal(np.asarray(a.index)[valid],
                                  np.asarray(b.index)[valid])
    np.testing.assert_array_equal(np.asarray(a.meta)[valid],
                                  np.asarray(b.meta)[valid])
    s = cold.stats()
    assert s["host_fetch_rows"] > 0     # the cold probe paid a host fetch
    assert s["device_fetch_rows"] > 0
    assert s["cold_clusters"] > 0


# ---------------------------------------------------------------------------
# Guard rails
# ---------------------------------------------------------------------------


def test_k_beyond_probe_budget_rejected(rng):
    store = mem.init_memory(mem.MemoryConfig(capacity=64, embed_dim=E,
                                             guide_len=G))
    ivf = IVFMemory(store, clusters=8, probes=1, bucket_cap=8)
    with pytest.raises(ValueError, match="candidate budget"):
        ivf.query_topk(jnp.asarray(_protos(rng, 1)[0]), 9)


def test_config_validation():
    from repro.core.rar import RARConfig
    cfg = mem.MemoryConfig(capacity=64, embed_dim=E, guide_len=G)
    with pytest.raises(ValueError):
        RARConfig(memory=cfg, retrieval_clusters=-1)
    with pytest.raises(ValueError):
        RARConfig(memory=cfg, retrieval_clusters=128)
    with pytest.raises(ValueError):
        RARConfig(memory=cfg, retrieval_clusters=8, retrieval_probes=0)
    with pytest.raises(ValueError):
        RARConfig(memory=cfg, retrieval_clusters=8, retrieval_probes=9)
    with pytest.raises(ValueError, match="journal"):
        RARConfig(memory=cfg, retrieval_clusters=8, journal_path="/tmp/x")
    with pytest.raises(TypeError):
        store = mem.init_memory(cfg)
        IVFMemory(IVFMemory(store, clusters=4), clusters=4)


def test_double_wrap_is_identity():
    from repro.core.rar import RARConfig
    cfg = RARConfig(memory=mem.MemoryConfig(capacity=64, embed_dim=E,
                                            guide_len=G),
                    retrieval_clusters=8, retrieval_probes=4)
    store = mem.init_memory(cfg.memory)
    w1 = wrap_store(store, cfg)
    assert isinstance(w1, IVFMemory)
    assert wrap_store(w1, cfg) is w1
