"""The in-place commit epoch: one fused, donated program per drain epoch.

Byte-exact equivalence with the value-semantics path (``add_batch`` →
``mark_soft`` → ``touch``, the order an epoch always had), the compiled
program's missing store copies, the commit stream's host pointer
mirror and counters, and the store views of a two-replica fabric after
the stream has given its old store up."""
import re

import jax.numpy as jnp
import numpy as np
import pytest
from test_fabric import build_fabric, serve_fabric
from test_pipeline import make_stream

from repro.core import memory as mem
from repro.serving.metrics import MetricsRegistry

FIELDS = ("emb", "mask", "guide", "hard", "added_at", "ptr")
E, G = 16, 4          # lanes pad to 128: the insert rows carry padding


def _store(capacity, filled, seed=0):
    """A store with ``filled`` inserts through the public value path
    (every third one hard, every other one with a guide)."""
    st = mem.init_memory(mem.MemoryConfig(capacity=capacity, embed_dim=E,
                                          guide_len=G))
    rng = np.random.default_rng(seed)
    for start in range(0, filled, capacity):
        k = min(capacity, filled - start)
        st = mem.add_batch(
            st, jnp.asarray(rng.normal(size=(k, E)), jnp.float32),
            jnp.asarray(rng.integers(0, 99, (k, G)), jnp.int32),
            jnp.asarray(np.arange(k) % 2 == 0),
            jnp.asarray(np.arange(k) % 3 == 0),
            jnp.arange(start, start + k, dtype=jnp.int32))
    return st


def _records(n, seed, now0=1000, e=E, g=G):
    rng = np.random.default_rng(seed)
    return [(now0 + int(t), rng.normal(size=e).astype(np.float32),
             rng.integers(0, 99, g).astype(np.int32), bool(t % 2),
             bool(t % 3 == 1))
            for t in rng.permutation(n)]


def _value_path(state, records, soft_clears, touches):
    """The epoch as the public value-semantics calls apply it: inserts
    sorted by ``now`` in chunks of at most C, then the soft-clears the
    eviction guard keeps, then the touches, last ``now`` winning."""
    records = sorted(records, key=lambda r: r[0])
    C = state.capacity
    base = int(state.ptr)
    end = base + len(records)

    def evicted(idx, snap):
        snap = base if snap is None else min(int(snap), base)
        return end - snap >= C or (idx - snap) % C < end - snap

    for s in range(0, len(records), C):
        chunk = records[s:s + C]
        state = mem.add_batch(
            state, jnp.asarray(np.stack([r[1] for r in chunk])),
            jnp.asarray(np.stack([r[2] for r in chunk])),
            jnp.asarray([r[3] for r in chunk]),
            jnp.asarray([r[4] for r in chunk]),
            jnp.asarray([r[0] for r in chunk], jnp.int32))
    softs = sorted({i for _, i, snap in soft_clears if not evicted(i, snap)})
    if softs:
        state = mem.mark_soft(state, jnp.asarray(softs, jnp.int32))
    last = {}
    for now, i, snap in sorted(touches, key=lambda t: t[:2]):
        if not evicted(i, snap):
            last[i] = now
    if last:
        idx = sorted(last)
        state = mem.touch(state, jnp.asarray(idx, jnp.int32),
                          jnp.asarray([last[i] for i in idx], jnp.int32))
    return state


def _host(state):
    return {f: np.asarray(getattr(state, f)) for f in FIELDS}


def _assert_bytes_equal(a, b):
    for f in FIELDS:
        assert a[f].dtype == b[f].dtype and a[f].shape == b[f].shape, f
        assert a[f].tobytes() == b[f].tobytes(), f


# (capacity, filled, inserts, soft_clears, touches); flag ops are
# (now, index, ptr_snapshot)
EPOCHS = {
    "inserts_only": (64, 10, 3, [], []),
    "flags_only": (64, 20, 0, [(5, 3, None), (6, 0, None)],
                   [(7, 4, None), (8, 9, None)]),
    "mixed_duplicate_touch": (64, 20, 5, [(5, 3, None)],
                              [(7, 4, None), (9, 4, None), (8, 4, None),
                               (6, 11, None)]),
    # snapshot 20 with 5 inserts at ptr 20: slots 20..24 were overwritten
    # since, so the ops on 21 and 24 drop and the ones on 3 and 25 land
    "guard_drops": (64, 20, 5, [(5, 21, 20), (6, 3, 20)],
                    [(7, 24, 20), (8, 25, 20)]),
    "ring_wrap": (16, 14, 5, [(5, 6, None)], [(7, 8, None)]),
    "above_bucket": (64, 30, 13, [(50 + i, i, None) for i in range(9)],
                     [(70 + i, 10 + i, None) for i in range(11)]),
    "above_capacity": (16, 16, 40, [(5, 2, None)], [(7, 5, None)]),
}


@pytest.mark.parametrize("case", sorted(EPOCHS))
def test_in_place_epoch_is_byte_identical_to_value_path(case):
    C, filled, k, softs, touches = EPOCHS[case]
    records = _records(k, seed=k)
    want = _host(_value_path(_store(C, filled), records, softs, touches))

    state = _store(C, filled)
    buf = mem.CommitBuffer()
    got, n = buf.apply_ops(state, records, softs, touches, donate=True)
    _assert_bytes_equal(_host(got), want)
    assert n == k and buf.epoch == 1 and buf.entries_applied == k
    # the store was given up: the epoch updated its buffers in place
    assert state.emb.is_deleted()

    # the value-semantics apply of the same epoch reads the same and
    # leaves its input whole
    kept = _store(C, filled)
    before = _host(kept)
    got, _ = mem.CommitBuffer().apply_ops(kept, records, softs, touches)
    _assert_bytes_equal(_host(got), want)
    _assert_bytes_equal(_host(kept), before)


def _copied_shapes(fn, state, ops):
    text = fn.lower(state, ops).compile().as_text()
    return {re.sub(r"\{.*", "", m)
            for m in re.findall(r"= (\S+) copy\(", text)}


def test_donated_epoch_program_copies_no_store_array():
    state = mem.init_memory(mem.MemoryConfig(capacity=4096, embed_dim=384,
                                             guide_len=8))
    ops = mem._epoch_operands(state, _records(3, seed=1, e=384, g=8), [5],
                              {6: 7})
    # emb, mask, guide, hard, added_at
    store = {"f32[4096,384]", "s32[4096,1]", "s32[4096,8]", "pred[4096]",
             "s32[4096]"}
    # the value-semantics program copies every store array first ...
    assert store <= _copied_shapes(mem._epoch_jit, state, ops)
    # ... the donated one at most the scalar pointer
    assert _copied_shapes(mem._epoch_donated_jit, state, ops) <= {"s32[]"}


@pytest.mark.parametrize("call", ["add", "add_batch", "mark_soft", "touch",
                                  "buffer_apply"])
def test_value_semantics_calls_keep_their_input(call):
    state = _store(32, 12)
    before = _host(state)
    if call == "add":
        out = mem.add(state, jnp.ones(E), jnp.ones(G, jnp.int32),
                      jnp.asarray(True), jnp.asarray(False), jnp.asarray(5))
    elif call == "add_batch":
        out = mem.add_batch(state, jnp.ones((2, E)),
                            jnp.ones((2, G), jnp.int32),
                            jnp.asarray([True, False]),
                            jnp.asarray([False, True]),
                            jnp.asarray([5, 6], jnp.int32))
    elif call == "mark_soft":
        out = mem.mark_soft(state, jnp.asarray([0, 3], jnp.int32))
    elif call == "touch":
        out = mem.touch(state, jnp.asarray([1], jnp.int32),
                        jnp.asarray([9], jnp.int32))
    else:
        buf = mem.CommitBuffer()
        buf.stage_add(np.ones(E, np.float32), np.ones(G, np.int32), True,
                      False, 50)
        buf.stage_touch(2, 51)
        out, _ = buf.apply(state)
    assert not any(getattr(state, f).is_deleted() for f in FIELDS)
    _assert_bytes_equal(_host(state), before)
    assert any(not np.array_equal(v, before[f])
               for f, v in _host(out).items())


def _stage(buf, k, now0, soft=None, touch=None):
    for now, emb, guide, hg, hard in _records(k, seed=now0, now0=now0):
        buf.stage_add(emb, guide, hg, hard, now)
    if soft is not None:
        buf.stage_soft_clear(soft, now0 + 100)
    if touch is not None:
        buf.stage_touch(touch, now0 + 101)


def test_stream_counts_epochs_in_place_and_one_pointer_read():
    reg = MetricsRegistry()
    stream = mem.CommitStream()
    stream.metrics = reg
    state = _store(32, 4)
    for e in range(3):
        _stage(stream.buffer, 3, 100 * e, soft=1, touch=2)
        given, state = state, stream.apply(state)
        assert given.emb.is_deleted()
    snap = reg.snapshot()
    assert snap["commit/epochs_applied"] == 3
    assert snap["commit/epochs_in_place"] == 3
    assert snap["host/syncs/commit"] == 1      # the mirror's first read
    assert stream.ptr_host == int(state.ptr) == 4 + 9 == stream.commits + 4


def _grown(wrapped):
    stream = mem.CommitStream()
    state = _store(16, 20 if wrapped else 10)
    _stage(stream.buffer, 3, 500)
    state = stream.apply(state)
    state, _ = stream.grow(state, 32)
    _stage(stream.buffer, 2, 600, touch=1)
    return stream, stream.apply(state)


@pytest.mark.parametrize("when", ["epochs", "grow", "grow_wrapped",
                                  "recovery"])
def test_host_pointer_mirror_matches_device(when, tmp_path):
    cfg = mem.MemoryConfig(capacity=16, embed_dim=E, guide_len=G)
    if when == "epochs":
        stream, state = mem.CommitStream(), _store(16, 14)
        for e in range(4):              # wraps the ring on the way
            _stage(stream.buffer, 3, 100 * e, touch=e)
            state = stream.apply(state)
    elif when in ("grow", "grow_wrapped"):
        stream, state = _grown(when == "grow_wrapped")
    else:
        path = str(tmp_path / "journal")
        stream, state, _ = mem.open_journaled_stream(path, cfg,
                                                     snapshot_every=2)
        state = mem.init_memory(cfg)
        for e in range(5):
            _stage(stream.buffer, 4, 100 * e, soft=e)
            state = stream.apply(state)
        stream.journal.close()
        stream, state, _ = mem.open_journaled_stream(path, cfg)
        stream.journal.close()
    assert stream.ptr_host == int(state.ptr)


def test_journaled_stream_recovery_replays_the_same_store(tmp_path):
    cfg = mem.MemoryConfig(capacity=16, embed_dim=E, guide_len=G)
    path = str(tmp_path / "journal")
    stream, _, _ = mem.open_journaled_stream(path, cfg, snapshot_every=3)
    state = mem.init_memory(cfg)
    for e in range(7):                  # wraps; snapshots at epochs 3, 6
        _stage(stream.buffer, 5, 100 * e, soft=e, touch=e + 1)
        state = stream.apply(state)
    want = _host(state)
    stream.journal.close()
    rec_state, epoch, applied, _ = mem.MemoryJournal.recover(path, cfg)
    _assert_bytes_equal(_host(rec_state), want)
    assert (epoch, applied) == (7, 35)


def test_fabric_views_hold_live_stores_after_in_place_commits():
    fab = build_fabric(replicas=2, memory=mem.MemoryConfig(
        capacity=16, embed_dim=16, guide_len=4))
    try:
        outs = serve_fabric(fab, make_stream(n_skills=9, reps=3), batch=3,
                            submit=True)
        assert len(outs) == 27
        fab.scale_to(3)                 # a view taken between epochs
        serve_fabric(fab, make_stream(n_skills=5, reps=1, seed=3),
                     batch=2, submit=True)
    finally:
        fab.close_shadow()
    stores = [r.memory for r in fab.replicas]
    assert all(s is stores[0] for s in stores)
    for s in stores:
        assert not any(getattr(s, f).is_deleted() for f in FIELDS)
    snap = fab.metrics()["registry"]
    assert snap["commit/epochs_in_place"] == \
        snap["commit/epochs_applied"] > 0
    assert fab.commit_stream.ptr_host == int(stores[0].ptr)
