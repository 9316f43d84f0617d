"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in repro.kernels.ref, plus the property-based top-k
parity sweep (random shapes, k, mask patterns and duplicate-similarity
ties — results must be bit-identical, tie-break order included)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.memory_topk import (MASK_GUIDE, MASK_VALID,
                                       memory_top1_batch_padded_pallas,
                                       memory_top1_batch_pallas,
                                       memory_top1_padded_pallas,
                                       memory_top1_pallas,
                                       memory_topk_batch_padded_pallas,
                                       memory_topk_padded_pallas,
                                       to_padded_layout)

TOL = {np.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# memory_top1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C", [64, 300, 1024, 4096])
@pytest.mark.parametrize("E", [128, 384])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_memory_top1_sweep(rng, C, E, dtype):
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    q = rng.normal(size=(E,)).astype(np.float32)
    q /= np.linalg.norm(q)
    mask = rng.random(C) < 0.6
    mask[int(rng.integers(0, C))] = True  # never empty
    mem_t = jnp.asarray(mem, dtype)
    s_ref, i_ref = ref.memory_top1(mem_t, jnp.asarray(q), jnp.asarray(mask))
    s_p, i_p = memory_top1_pallas(mem_t, jnp.asarray(q), jnp.asarray(mask),
                                  block_c=128, interpret=True)
    assert int(i_ref) == int(i_p)
    np.testing.assert_allclose(float(s_ref), float(s_p), atol=1e-5)


def test_memory_top1_empty_mask(rng):
    mem = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    mask = jnp.zeros((64,), bool)
    s, _ = memory_top1_pallas(mem, q, mask, block_c=32, interpret=True)
    assert float(s) == -2.0


def test_memory_top1_exact_hit(rng):
    """Query equal to a stored row must retrieve that row with sim≈1."""
    mem = rng.normal(size=(256, 384)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    q = mem[123]
    mask = np.ones(256, bool)
    s, i = memory_top1_pallas(jnp.asarray(mem), jnp.asarray(q),
                              jnp.asarray(mask), block_c=64, interpret=True)
    assert int(i) == 123
    assert float(s) > 0.999


# ---------------------------------------------------------------------------
# memory_top1_batch (multi-query)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C", [64, 300, 1024])
@pytest.mark.parametrize("B", [1, 3, 8, 32])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_memory_top1_batch_sweep(rng, C, B, dtype):
    E = 384
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    qs = rng.normal(size=(B, E)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    mask = rng.random(C) < 0.6
    mask[int(rng.integers(0, C))] = True  # never empty
    mem_t = jnp.asarray(mem, dtype)
    s_ref, i_ref = ref.memory_top1_batch(mem_t, jnp.asarray(qs),
                                         jnp.asarray(mask))
    s_p, i_p = memory_top1_batch_pallas(mem_t, jnp.asarray(qs),
                                        jnp.asarray(mask), block_c=128,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_p),
                               atol=1e-5)


def test_memory_top1_batch_matches_single(rng):
    """Each batched query must agree with the single-query kernel."""
    C, E, B = 256, 128, 7
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    qs = rng.normal(size=(B, E)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    mask = jnp.asarray(rng.random(C) < 0.7)
    s_b, i_b = memory_top1_batch_pallas(jnp.asarray(mem), jnp.asarray(qs),
                                        mask, block_c=64, interpret=True)
    for b in range(B):
        s1, i1 = memory_top1_pallas(jnp.asarray(mem), jnp.asarray(qs[b]),
                                    mask, block_c=64, interpret=True)
        assert int(i1) == int(i_b[b])
        np.testing.assert_allclose(float(s1), float(s_b[b]), atol=1e-6)


def test_memory_top1_batch_empty_mask(rng):
    mem = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    qs = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    mask = jnp.zeros((64,), bool)
    s, _ = memory_top1_batch_pallas(mem, qs, mask, block_c=32,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(s), np.full(4, -2.0))


def test_memory_top1_batch_exact_hits(rng):
    """Queries equal to stored rows retrieve those rows with sim≈1."""
    mem = rng.normal(size=(256, 384)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    picks = [3, 77, 200]
    qs = mem[picks]
    mask = np.ones(256, bool)
    s, i = memory_top1_batch_pallas(jnp.asarray(mem), jnp.asarray(qs),
                                    jnp.asarray(mask), block_c=64,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(i), picks)
    assert float(np.min(np.asarray(s))) > 0.999


# ---------------------------------------------------------------------------
# memory_top1 padded entry points (the zero-copy serving path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C", [64, 300, 1024])
@pytest.mark.parametrize("E", [128, 384])
def test_memory_top1_padded_matches_oracle(rng, C, E):
    """Padded Pallas entry == padded oracle == legacy oracle, for both the
    valid view and the valid+guide view of the mask bit plane."""
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    q = rng.normal(size=(E,)).astype(np.float32)
    q /= np.linalg.norm(q)
    valid = rng.random(C) < 0.7
    has_guide = rng.random(C) < 0.4
    valid[int(rng.integers(0, C))] = True
    bits = (valid.astype(np.int32) * MASK_VALID
            + (valid & has_guide).astype(np.int32) * MASK_GUIDE)
    memp, maskp = to_padded_layout(jnp.asarray(mem), jnp.asarray(bits),
                                   block_c=128)
    for required, legacy_mask in ((MASK_VALID, valid),
                                  (MASK_VALID | MASK_GUIDE,
                                   valid & has_guide)):
        if not legacy_mask.any():
            continue
        s_l, i_l = ref.memory_top1(jnp.asarray(mem), jnp.asarray(q),
                                   jnp.asarray(legacy_mask))
        s_o, i_o = ref.memory_top1_padded(memp, jnp.asarray(q), maskp,
                                          required)
        s_p, i_p = memory_top1_padded_pallas(memp, jnp.asarray(q), maskp,
                                             required=required, block_c=128,
                                             interpret=True)
        assert int(i_l) == int(i_o) == int(i_p)
        np.testing.assert_allclose(float(s_l), float(s_p), atol=1e-5)
        np.testing.assert_allclose(float(s_o), float(s_p), atol=1e-5)


@pytest.mark.parametrize("B", [1, 5, 32])
def test_memory_top1_batch_padded_matches_oracle(rng, B):
    C, E = 300, 384
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    qs = rng.normal(size=(B, E)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    valid = rng.random(C) < 0.7
    has_guide = rng.random(C) < 0.4
    valid[int(rng.integers(0, C))] = True
    has_guide[valid.argmax()] = True
    bits = (valid.astype(np.int32) * MASK_VALID
            + (valid & has_guide).astype(np.int32) * MASK_GUIDE)
    memp, maskp = to_padded_layout(jnp.asarray(mem), jnp.asarray(bits),
                                   block_c=128)
    for required, legacy_mask in ((MASK_VALID, valid),
                                  (MASK_VALID | MASK_GUIDE,
                                   valid & has_guide)):
        s_l, i_l = ref.memory_top1_batch(jnp.asarray(mem), jnp.asarray(qs),
                                         jnp.asarray(legacy_mask))
        s_o, i_o = ref.memory_top1_batch_padded(memp, jnp.asarray(qs),
                                                maskp, required)
        s_p, i_p = memory_top1_batch_padded_pallas(
            memp, jnp.asarray(qs), maskp, required=required, block_c=128,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(i_l), np.asarray(i_p))
        np.testing.assert_array_equal(np.asarray(i_o), np.asarray(i_p))
        np.testing.assert_allclose(np.asarray(s_l), np.asarray(s_p),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_o), np.asarray(s_p),
                                   atol=1e-5)


def test_query_path_is_zero_copy():
    """No store-sized buffer is materialized inside the jitted query —
    top-1 or top-k: no jaxpr equation *produces* a (Cp, Ep)-shaped value;
    the store only enters as an input operand (the old wrappers created a
    second full-size buffer via zeros+scatter on every call)."""
    import re

    import jax

    from repro.core import memory as cmem

    cfg = cmem.MemoryConfig(capacity=1024, embed_dim=384, guide_len=4)
    state = cmem.init_memory(cfg)
    q = jnp.zeros((cfg.embed_dim,), jnp.float32)
    qs = jnp.zeros((8, cfg.embed_dim), jnp.float32)
    Cp, Ep = state.emb.shape
    # equation outputs print as `name:f32[Cp,Ep] = prim ...`
    produced = re.compile(rf":f32\[{Cp},{Ep}\] =")
    for jaxpr in (jax.make_jaxpr(
                      lambda s, e: cmem._query_jit(s, e))(state, q),
                  jax.make_jaxpr(
                      lambda s, e: cmem._query_batch_jit(s, e))(state, qs),
                  jax.make_jaxpr(
                      lambda s, e: cmem._query_topk_jit(s, e, 4))(state, q),
                  jax.make_jaxpr(
                      lambda s, e: cmem._query_topk_batch_jit(s, e, 4))(
                          state, qs)):
        assert not produced.search(str(jaxpr)), jaxpr


# ---------------------------------------------------------------------------
# memory_topk (the multi-guide read path)
# ---------------------------------------------------------------------------


def _topk_store(rng, C, E, density, n_dups):
    """Random store with controlled mask density and ``n_dups`` exact
    duplicates of row 0 (duplicate similarities → the tie-break path)."""
    mem = rng.normal(size=(C, E)).astype(np.float32)
    norms = np.linalg.norm(mem, axis=1, keepdims=True)
    mem /= np.where(norms > 0, norms, 1.0)
    dup_rows = 1 + (np.arange(n_dups) * max(1, (C - 1) // (n_dups + 1))
                    ) % max(C - 1, 1)
    mem[dup_rows] = mem[0]
    valid = rng.random(C) < density
    has_guide = rng.random(C) < 0.5
    bits = (valid.astype(np.int32) * MASK_VALID
            + (valid & has_guide).astype(np.int32) * MASK_GUIDE)
    return mem, bits


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([1, 2, 4, 8]),                  # k
       st.sampled_from([17, 64, 100, 300]),            # C (odd → padding)
       st.sampled_from([16, 128, 384]),                # E
       st.sampled_from([1, 2, 5, 32]),                 # B
       st.sampled_from([0.0, 0.2, 0.7, 1.0]),          # mask density
       st.sampled_from([0, 3, 7]),                     # duplicate rows
       st.booleans())                                  # guides-only view
def test_property_topk_pallas_matches_oracle(seed, k, C, E, B, density,
                                             n_dups, guides_only):
    """Property sweep: the Pallas top-k kernel must reproduce the ref
    oracle's *retrieval* bit-for-bit — the returned rows, their order
    (duplicate-similarity ties resolve to ascending store row in both)
    and the -2.0 sentinel fill when k exceeds the view's population.
    Similarities are exact to 1 ulp across the two implementations (the
    kernel's lane-padded query block takes a different BLAS gemm shape
    than the oracle's compact one — bitwise-equal dot products across
    matmul shapes are not a portable property of any backend) and
    *bitwise* equal within each implementation at tied rows, which is
    what makes the tie order deterministic. The dispatch-path pins
    (k=1 ≡ top-1, sharded ≡ single-device) compare like against like
    and are asserted fully bitwise elsewhere."""
    rng = np.random.default_rng(seed)
    mem, bits = _topk_store(rng, C, E, density, n_dups)
    qs = rng.normal(size=(B, E)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    qs[0] = mem[0]                     # exact hit on the duplicated row
    memp, maskp = to_padded_layout(jnp.asarray(mem), jnp.asarray(bits),
                                   block_c=128)
    required = MASK_VALID | (MASK_GUIDE if guides_only else 0)

    s_o, i_o = ref.memory_topk_batch_padded(memp, jnp.asarray(qs), maskp,
                                            k, required)
    s_p, i_p = memory_topk_batch_padded_pallas(
        memp, jnp.asarray(qs), maskp, k=k, required=required, block_c=128,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(i_o), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(s_o), np.asarray(s_p),
                               atol=1e-6)

    s1_o, i1_o = ref.memory_topk_padded(memp, jnp.asarray(qs[0]), maskp,
                                        k, required)
    s1_p, i1_p = memory_topk_padded_pallas(
        memp, jnp.asarray(qs[0]), maskp, k=k, required=required,
        block_c=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(i1_o), np.asarray(i1_p))
    np.testing.assert_allclose(np.asarray(s1_o), np.asarray(s1_p),
                               atol=1e-6)

    # structural invariants of the result order, in BOTH implementations:
    # sims strictly descending except at ties, ties in ascending row
    # order with bitwise-equal sims
    for s_row, i_row in ((np.asarray(s_o), np.asarray(i_o)),
                         (np.asarray(s_p), np.asarray(i_p)),
                         (np.asarray(s1_o)[None], np.asarray(i1_o)[None]),
                         (np.asarray(s1_p)[None], np.asarray(i1_p)[None])):
        for b in range(s_row.shape[0]):
            for j in range(k - 1):
                assert (s_row[b, j] > s_row[b, j + 1]
                        or (s_row[b, j] == s_row[b, j + 1]
                            and i_row[b, j] < i_row[b, j + 1]))


def test_topk_tie_order_is_lowest_row_first(rng):
    """Duplicated store rows must surface in ascending row order, in both
    the oracle and the kernel, at every k that spans the duplicates."""
    C, E = 96, 64
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    dups = [5, 17, 40, 77]
    mem[dups] = mem[dups[0]]
    bits = np.full(C, MASK_VALID, np.int32)
    memp, maskp = to_padded_layout(jnp.asarray(mem), jnp.asarray(bits),
                                   block_c=32)
    q = jnp.asarray(mem[dups[0]])
    for k in (1, 2, 4):
        s_o, i_o = ref.memory_topk_padded(memp, q, maskp, k, MASK_VALID)
        _, i_p = memory_topk_padded_pallas(memp, q, maskp, k=k,
                                           required=MASK_VALID, block_c=32,
                                           interpret=True)
        assert list(np.asarray(i_o))[:min(k, 4)] == dups[:min(k, 4)]
        np.testing.assert_array_equal(np.asarray(i_o), np.asarray(i_p))
        assert float(np.asarray(s_o)[0]) > 0.999


def test_topk_k1_matches_top1_kernels(rng):
    """k=1 output must match the top-1 kernels row for row (the top-1
    data plane is the k=1 special case, not a separate contract)."""
    C, E, B = 200, 128, 6
    mem = rng.normal(size=(C, E)).astype(np.float32)
    mem /= np.linalg.norm(mem, axis=1, keepdims=True)
    qs = rng.normal(size=(B, E)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    valid = rng.random(C) < 0.6
    valid[3] = True
    bits = valid.astype(np.int32) * MASK_VALID
    memp, maskp = to_padded_layout(jnp.asarray(mem), jnp.asarray(bits),
                                   block_c=64)
    s1, i1 = memory_top1_batch_padded_pallas(memp, jnp.asarray(qs), maskp,
                                             block_c=64, interpret=True)
    sk, ik = memory_topk_batch_padded_pallas(memp, jnp.asarray(qs), maskp,
                                             k=1, required=MASK_VALID,
                                             block_c=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(ik)[:, 0])
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(sk)[:, 0])


def test_topk_rejects_bad_k():
    memp = jnp.zeros((64, 128), jnp.float32)
    maskp = jnp.zeros((64, 1), jnp.int32)
    q = jnp.zeros((128,), jnp.float32)
    with pytest.raises(ValueError):
        memory_topk_padded_pallas(memp, q, maskp, k=0, interpret=True)
    with pytest.raises(ValueError):
        # k beyond the kernel block cannot keep the accumulator exact
        memory_topk_padded_pallas(memp, q, maskp, k=16, block_c=8,
                                  interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,window", [(128, 0), (256, 0), (256, 64),
                                      (512, 128), (256, 32)])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, S, window, H, KV, dtype):
    B, hd = 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    o_ref = ref.flash_attention(q, k, v, causal=True, window=window)
    o_p = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=64, block_k=64, interpret=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal(rng):
    B, S, H, hd = 1, 128, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    o_ref = ref.flash_attention(q, k, v, causal=False)
    o_p = flash_attention_pallas(q, k, v, causal=False, block_q=64,
                                 block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_block_skip_equals_masked(rng):
    """Window smaller than a block: skipped blocks must not change the
    result (the FLOPs-saving path is numerically identical)."""
    B, S, H, hd = 1, 512, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    o_ref = ref.flash_attention(q, k, v, causal=True, window=16)
    o_p = flash_attention_pallas(q, k, v, causal=True, window=16,
                                 block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,clen,window", [
    (256, 256, 0), (512, 300, 0), (512, 300, 64), (1024, 1000, 256),
    (256, 1, 0)])
@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_attention_sweep(rng, M, clen, window, H, KV, dtype):
    B, hd = 2, 32
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, M, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, M, KV, hd)), dtype)
    cl = jnp.asarray(clen, jnp.int32)
    o_ref = ref.decode_attention(q, k, v, cl, window=window)
    o_p = decode_attention_pallas(q, k, v, cl, window=window, block_m=128,
                                  interpret=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


def test_decode_matches_flash_at_full_length(rng):
    """decode(q_last) == flash(q)[last] when the cache is exactly full."""
    B, S, H, hd = 1, 256, 4, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    full = ref.flash_attention(q, k, v, causal=True)
    dec = decode_attention_pallas(q[:, -1], k, v, jnp.asarray(S, jnp.int32),
                                  block_m=64, interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# dispatch-layer impl selection
# ---------------------------------------------------------------------------


def test_impl_selection_memoized_with_override(monkeypatch):
    """ops resolves the kernel impl once (no per-dispatch env/backend
    probe); set_impl is the explicit override hook and set_impl(None)
    re-resolves from the environment."""
    from repro.kernels import ops

    saved = ops._impl_cache
    try:
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
        ops.set_impl(None)
        assert ops.default_impl() == "interpret"
        # memoized: flipping the env after first resolution has no effect
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
        assert ops.default_impl() == "interpret"
        # the override hook wins immediately
        ops.set_impl("ref")
        assert ops.default_impl() == "ref"
        with pytest.raises(ValueError):
            ops.set_impl("bogus")
    finally:
        ops._impl_cache = saved


@pytest.mark.parametrize("case", ["default", "interpret", "ref", "error"])
def test_impl_selection_on_tpu_backend(monkeypatch, case):
    """On a TPU backend the dispatch serves the Pallas kernels: the
    interpreter is refused, the jnp reference is reported, and a backend
    error propagates instead of turning into the CPU path."""
    from repro.kernels import ops

    saved = ops._impl_cache
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    if case == "error":
        def no_backend():
            raise RuntimeError("no backend")
        monkeypatch.setattr(ops.jax, "default_backend", no_backend)
    else:
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    try:
        ops.set_impl(None)
        if case == "default":
            assert ops.default_impl() == "pallas"
        elif case == "interpret":
            with pytest.raises(ValueError, match="TPU"):
                ops.set_impl("interpret")
            monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
            with pytest.raises(ValueError, match="TPU"):
                ops.default_impl()
        elif case == "ref":
            monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
            with pytest.warns(UserWarning, match="reference kernels"):
                assert ops.default_impl() == "ref"
        else:
            with pytest.raises(RuntimeError, match="no backend"):
                ops.default_impl()
    finally:
        ops._impl_cache = saved


def test_odd_block_c_never_crashes(rng):
    """block_c values that are not row-tile multiples (or smaller than the
    tile) must still produce a valid blocking, not a ZeroDivisionError."""
    C, E = 100, 128
    mem = rng.normal(size=(C, E)).astype(np.float32)
    q = rng.normal(size=(E,)).astype(np.float32)
    mask = np.ones(C, bool)
    s_ref, i_ref = ref.memory_top1(jnp.asarray(mem), jnp.asarray(q),
                                   jnp.asarray(mask))
    for bc in (4, 12, 100):
        s, i = memory_top1_pallas(jnp.asarray(mem), jnp.asarray(q),
                                  jnp.asarray(mask), block_c=bc,
                                  interpret=True)
        assert int(i) == int(i_ref)
        np.testing.assert_allclose(float(s), float(s_ref), atol=1e-5)
