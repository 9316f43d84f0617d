"""Sharded memory store: single-shard behaviour in-process, multi-shard
parity (identical rows incl. tie-breaks, sims within a few ulp) via a subprocess with forced host
placeholder devices (XLA device count must be set before jax initializes),
and the microbatched controller serving against the sharded store."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from test_rar_controller import FakeTier, greq, make_cfg, prompt, skill_emb

from repro.core import memory as mem
from repro.core.memory_sharded import ShardedMemory
from repro.core.pipeline import MicrobatchRAR

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CFG = mem.MemoryConfig(capacity=32, embed_dim=16, guide_len=4)


def rand_unit(rng, d=16):
    v = rng.normal(size=d).astype(np.float32)
    return v / np.linalg.norm(v)


def test_single_shard_matches_memory_state(rng):
    """With however many devices this host has (1 in CI), the sharded
    store must agree with MemoryState exactly on a mixed workload."""
    single = mem.init_memory(CFG)
    sharded = ShardedMemory(CFG)
    embs = np.stack([rand_unit(rng) for _ in range(10)])
    guides = np.arange(40, dtype=np.int32).reshape(10, 4)
    hg = np.arange(10) % 2 == 0
    hd = np.arange(10) % 3 == 0
    now = np.arange(10, dtype=np.int32)
    args = (jnp.asarray(embs), jnp.asarray(guides), jnp.asarray(hg),
            jnp.asarray(hd), jnp.asarray(now))
    single = mem.add_batch(single, *args)
    sharded.add_batch(*args)
    assert sharded.size_fast == single.size_fast == 10

    qs = np.stack([rand_unit(rng) for _ in range(4)])
    qs[0] = embs[3]
    for guides_only in (False, True):
        a = mem.query_batch(single, jnp.asarray(qs),
                            guides_only=guides_only).device_get()
        b = mem.query_batch(sharded, jnp.asarray(qs),
                            guides_only=guides_only).device_get()
        np.testing.assert_array_equal(a.sim, b.sim)
        np.testing.assert_array_equal(a.meta, b.meta)

    # flag updates hit the replicated metadata identically
    single = mem.mark_soft(single, jnp.int32(0))
    sharded.mark_soft(jnp.int32(0))
    single = mem.touch(single, jnp.int32(2), jnp.int32(99))
    sharded.touch(jnp.int32(2), jnp.int32(99))
    st = sharded.to_single_device()
    for f in ("guide", "hard", "added_at", "ptr", "emb", "mask"):
        np.testing.assert_array_equal(np.asarray(getattr(single, f)),
                                      np.asarray(getattr(st, f)), f)


def test_sharded_wraparound_and_overflow(rng):
    sharded = ShardedMemory(CFG)
    for i in range(CFG.capacity + 5):
        sharded.add(jnp.asarray(rand_unit(rng)), jnp.zeros(4, jnp.int32),
                    False, False, np.int32(i))
    assert sharded.size_fast == CFG.capacity
    assert int(sharded.ptr) == CFG.capacity + 5
    with pytest.raises(ValueError):
        sharded.add_batch(
            jnp.zeros((CFG.capacity + 1, 16), jnp.float32),
            jnp.zeros((CFG.capacity + 1, 4), jnp.int32),
            jnp.zeros(CFG.capacity + 1, bool),
            jnp.zeros(CFG.capacity + 1, bool),
            jnp.zeros(CFG.capacity + 1, jnp.int32))


def test_capacity_must_divide_shards():
    import jax

    if len(jax.devices()) == 1:
        sharded = ShardedMemory(mem.MemoryConfig(capacity=31, embed_dim=16,
                                                 guide_len=4))
        assert sharded.shards == 1          # everything divides 1
    else:
        with pytest.raises(ValueError):
            ShardedMemory(mem.MemoryConfig(capacity=31, embed_dim=16,
                                           guide_len=4))


def test_multi_shard_parity_subprocess():
    """4 forced host devices: sharded rows — and the full packed
    metadata — identical to single-device, tie-breaks included, and sims
    within a few ulp (the two stores multiply different matrix shapes)."""
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=4").strip()
    env = dict(os.environ,
               PYTHONPATH=SRC,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=flags)
    r = subprocess.run([sys.executable, "-m", "repro.core.memory_sharded"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["shards"] == 4
    assert report["rows_meta_identical"] is True
    assert report["sim_max_ulp"] <= 4
    assert report["checks"] > 0
    # the selftest must have exercised the top-k merge across shards
    # (global top-k == single-device top-k on the same ring, ties incl.)
    assert report["topk_checked"] == [1, 2, 4, 8]
    # ... and the deferred-commit sweep: epoch-buffered commits (shuffled
    # staging + flag updates) bit-identical across both store flavours
    assert report["deferred_commit_epochs"] > 0


def test_single_shard_topk_matches_memory_state(rng):
    """Sharded top-k agrees bit-for-bit with MemoryState on this host's
    mesh (1 shard in CI; the 4-shard merge runs in the subprocess test)."""
    single = mem.init_memory(CFG)
    sharded = ShardedMemory(CFG)
    embs = np.stack([rand_unit(rng) for _ in range(12)])
    embs[5] = embs[1]              # duplicate row → tie-break path
    guides = np.arange(48, dtype=np.int32).reshape(12, 4)
    hg = np.arange(12) % 2 == 0
    hd = np.arange(12) % 3 == 0
    now = np.arange(12, dtype=np.int32)
    args = (jnp.asarray(embs), jnp.asarray(guides), jnp.asarray(hg),
            jnp.asarray(hd), jnp.asarray(now))
    single = mem.add_batch(single, *args)
    sharded.add_batch(*args)
    qs = np.stack([rand_unit(rng) for _ in range(4)])
    qs[0] = embs[1]
    for guides_only in (False, True):
        for k in (1, 2, 4, 8):
            a = mem.query_topk_batch(single, jnp.asarray(qs), k,
                                     guides_only=guides_only).device_get()
            b = sharded.query_topk_batch(jnp.asarray(qs), k,
                                         guides_only=guides_only
                                         ).device_get()
            np.testing.assert_array_equal(a.sim, b.sim)
            np.testing.assert_array_equal(a.meta, b.meta)
            a1 = mem.query_topk(single, jnp.asarray(qs[0]), k,
                                guides_only=guides_only).device_get()
            b1 = sharded.query_topk(jnp.asarray(qs[0]), k,
                                    guides_only=guides_only).device_get()
            np.testing.assert_array_equal(a1.sim, b1.sim)
            np.testing.assert_array_equal(a1.meta, b1.meta)


def test_sharded_topk_rejects_k_past_shard_rows():
    """k must not exceed the logical rows per shard (the merge would see
    local padding rows whose global slots collide with the next shard)."""
    import jax

    sharded = ShardedMemory(CFG)
    if len(jax.devices()) == 1:
        # single shard: the capacity bound is the only limit
        with pytest.raises(ValueError):
            sharded.query_topk(jnp.zeros(16), CFG.capacity + 1)
    else:
        with pytest.raises(ValueError):
            sharded.query_topk(jnp.zeros(16),
                               CFG.capacity // len(jax.devices()) + 1)


def build_batched(memory=None, **cfg_kw):
    weak = FakeTier(known={0, 1}, name="weak")
    strong = FakeTier(known=range(10_000), can_guide=True, name="strong")
    holder = {}
    ctrl = MicrobatchRAR(weak, strong, lambda p: holder["emb"],
                         lambda e, k: False, make_cfg(**cfg_kw),
                         memory=memory)
    return ctrl


def test_controller_serves_against_sharded_store():
    """MicrobatchRAR with an injected ShardedMemory produces the same
    Outcome stream and store contents as with the default MemoryState."""
    cfg_kw = dict()
    stream = [(s, x) for x in range(3) for s in range(5)]

    plain = build_batched(**cfg_kw)
    shard = build_batched(memory=ShardedMemory(plain.cfg.memory), **cfg_kw)
    for ctrl in (plain, shard):
        outs = []
        for start in range(0, len(stream), 4):
            chunk = stream[start:start + 4]
            outs += ctrl.process_batch(
                [prompt(s, x) for s, x in chunk],
                [greq(s) for s, _ in chunk],
                keys=chunk,
                embs=np.stack([skill_emb(s) for s, _ in chunk]))
        ctrl.outs = outs
    assert plain.outs == shard.outs
    assert plain.weak.engine.calls == shard.weak.engine.calls
    assert plain.strong.engine.calls == shard.strong.engine.calls
    st = shard.memory.to_single_device()
    for f in ("emb", "mask", "guide", "hard", "added_at", "ptr"):
        np.testing.assert_array_equal(np.asarray(getattr(plain.memory, f)),
                                      np.asarray(getattr(st, f)), f)
