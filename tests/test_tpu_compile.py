"""Compile rehearsals of the main-path retrieval kernels for a described
TPU v5e (``v5e:2x2``), without a chip: each compiles at the serving width
(E=384, a C=65536 store) and must lower to the Pallas kernel
(``tpu_custom_call``), which interpret-mode tests cannot show. A compile
that passes is not a chip run: nothing here runs or times anything.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU compiler
library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core import memory as mem
from repro.core.memory_sharded import (AXIS, _query_topk_batch_sharded,
                                       _query_topk_sharded, make_memory_mesh)
from repro.kernels import ops
from repro.kernels.memory_ivf import (ivf_route_batch_padded_pallas,
                                      ivf_route_padded_pallas)
from repro.kernels.memory_topk import (memory_top1_batch_padded_pallas,
                                       memory_top1_padded_pallas,
                                       memory_topk_batch_padded_pallas,
                                       memory_topk_padded_pallas,
                                       padded_lanes, padded_rows)

E = 384                 # the embedder's width (configs.rar_system)
C = 65536               # guide-store rows
CLUSTERS = C // 64      # IVF centroid plane, as memory_bench sizes it
PROBES = 4
SHARDS = 4


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 topology. The persistent compilation cache
    is off around these compiles: an entry written for a chip that is
    not attached cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        compilation_cache.reset_cache()
        if saved_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _queries(b: int, sharding):
    shape = (E,) if b == 1 else (b, E)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _plane(rows: int, sharding):
    """A (rows, E) plane and its int32 mask bit plane in kernel layout."""
    rp, ep = padded_rows(rows), padded_lanes(E)
    return (jax.ShapeDtypeStruct((rp, ep), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((rp, 1), jnp.int32, sharding=sharding))


def _kernel(name: str, b: int):
    """The dispatch path's kernel entry for one query (b == 1) or a
    batch, with its static arguments bound."""
    single = b == 1
    if name == "top1":
        fn = memory_top1_padded_pallas if single \
            else memory_top1_batch_padded_pallas
        return fn
    if name.startswith("topk"):
        k = int(name[4:])
        fn = memory_topk_padded_pallas if single \
            else memory_topk_batch_padded_pallas
        return lambda m, q, mask: fn(m, q, mask, k=k)
    fn = ivf_route_padded_pallas if single else ivf_route_batch_padded_pallas
    return lambda m, q, mask: fn(m, q, mask, n_probe=PROBES)


@pytest.mark.parametrize("b", [1, 32])
@pytest.mark.parametrize("name", ["top1", "topk1", "topk4", "ivf_route"])
def test_kernel_compiles_for_v5e(one_chip, name, b):
    rows = CLUSTERS if name == "ivf_route" else C
    plane, mask = _plane(rows, one_chip)
    text = _compiled_text(_kernel(name, b), plane, _queries(b, one_chip),
                          mask)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 32])
def test_sharded_topk_compiles_across_four_chips(topo, b):
    """The row-sharded store's top-k read: the per-shard kernel plus the
    cross-chip candidate gather, over four described chips."""
    mesh = make_memory_mesh(devices=list(topo.devices)[:SHARDS])
    cs = C // SHARDS
    rows = NamedSharding(mesh, P(AXIS, None))
    repl = NamedSharding(mesh, P())
    ep = padded_lanes(E)
    emb = jax.ShapeDtypeStruct((SHARDS * padded_rows(cs), ep), jnp.float32,
                               sharding=rows)
    mask = jax.ShapeDtypeStruct((SHARDS * padded_rows(cs), 1), jnp.int32,
                                sharding=rows)
    query = _query_topk_sharded if b == 1 else _query_topk_batch_sharded
    saved = ops._impl_cache
    # dispatch would pick the jnp reference for this process's CPU
    # backend; the described chip takes the Pallas kernel
    ops.set_impl("pallas")
    try:
        compiled = query.lower(mesh, cs, 4, 1, emb, mask,
                               _queries(b, repl)).compile()
    finally:
        ops._impl_cache = saved
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the candidate gather: small gathers may lower to an all-reduce
    assert "all-gather" in text or "all-reduce" in text
    # each chip holds one shard of the store, not the whole of it
    args = compiled.memory_analysis().argument_size_in_bytes
    assert padded_rows(cs) * ep * 4 <= args < SHARDS * cs * ep * 4


def test_commit_epoch_updates_the_store_in_place_on_v5e(one_chip):
    """The commit stream's donated epoch program at the benchmark's store
    size (2^20 rows, guide blocks of 8): every store byte is aliased from
    input to output and no store array is copied, where the
    value-semantics program of the same body aliases nothing."""
    rows, guide_len = 1 << 20, 8

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    store = mem.MemoryState(
        emb=arg((padded_rows(rows), padded_lanes(E)), jnp.float32),
        mask=arg((padded_rows(rows), 1), jnp.int32),
        guide=arg((rows, guide_len), jnp.int32),
        hard=arg((rows,), jnp.bool_), added_at=arg((rows,), jnp.int32),
        ptr=arg((), jnp.int32))
    ops = arg((8, padded_lanes(E) + guide_len + 6), jnp.int32)
    store_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(store)) - 4   # all but ptr
    donated = mem._epoch_donated_jit.lower(store, ops).compile()
    assert donated.memory_analysis().alias_size_in_bytes >= store_bytes
    assert not re.search(r"copy(-start)?\(%state_(emb|mask|guide|hard|"
                         r"added_at)", donated.as_text())
    plain = mem._epoch_jit.lower(store, ops).compile()
    assert plain.memory_analysis().alias_size_in_bytes == 0
