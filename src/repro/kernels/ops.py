"""Jit'd dispatch layer over the Pallas kernels and their jnp oracles.

Selection order:
* an explicit :func:`set_impl` override (tests) wins,
* then ``REPRO_KERNEL_IMPL=ref|pallas|interpret`` env var,
* otherwise: ``pallas`` on TPU backends, ``ref`` elsewhere (CPU hosts).
  ``interpret`` runs the Pallas kernel bodies in Python — used by the test
  suite to validate the TPU kernels against the oracles.

The backend is never guessed: an error from ``jax.default_backend()``
propagates. On a TPU backend ``interpret`` is refused, and ``ref`` (the
jnp oracles) is reported with a warning, since either would hide the chip
behind a slower path.

The selection is resolved **once** and memoized: the old per-dispatch
``os.environ`` read + ``jax.default_backend()`` probe sat on the hot loop
(every memory query / attention call paid it). Resolution is lazy — first
dispatch, not import — so importing this module never touches jax backend
state. Tests flip implementations via :func:`set_impl`; ``set_impl(None)``
re-resolves from the environment.
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.memory_ivf import (ivf_route_batch_padded_pallas,
                                      ivf_route_padded_pallas)
from repro.kernels.memory_topk import (MASK_VALID,
                                       memory_top1_batch_padded_pallas,
                                       memory_top1_batch_pallas,
                                       memory_top1_padded_pallas,
                                       memory_top1_pallas,
                                       memory_topk_batch_padded_pallas,
                                       memory_topk_padded_pallas)

IMPLS = ("ref", "pallas", "interpret")

_impl_cache: str | None = None


def _checked(impl: str, source: str) -> str:
    """Validate a requested implementation against the backend."""
    if impl not in IMPLS:
        raise ValueError(f"{source}={impl!r}: expected {'|'.join(IMPLS)}")
    if impl != "pallas" and jax.default_backend() == "tpu":
        if impl == "interpret":
            raise ValueError(f"{source}=interpret on a TPU backend: the "
                             f"Pallas interpreter is for CPU tests")
        warnings.warn(f"{source}=ref on a TPU backend: serving the jnp "
                      f"reference kernels, not the Pallas kernels",
                      stacklevel=3)
    return impl


def set_impl(impl: str | None) -> None:
    """Override the kernel implementation (``ref``/``pallas``/
    ``interpret``), or ``None`` to re-resolve from the environment on the
    next dispatch. The explicit hook for tests — mutating
    ``REPRO_KERNEL_IMPL`` after the first dispatch has no effect."""
    global _impl_cache
    _impl_cache = None if impl is None else _checked(impl, "set_impl")


def default_impl() -> str:
    """The memoized implementation the dispatch serves with (resolved on
    first call) — also what a run reports beside its device."""
    global _impl_cache
    if _impl_cache is None:
        env = os.environ.get("REPRO_KERNEL_IMPL")
        if env:
            _impl_cache = _checked(env, "REPRO_KERNEL_IMPL")
        else:
            _impl_cache = ("pallas" if jax.default_backend() == "tpu"
                           else "ref")
    return _impl_cache


def memory_top1(mem: jax.Array, q: jax.Array, mask: jax.Array,
                impl: str | None = None) -> tuple[jax.Array, jax.Array]:
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_top1(mem, q, mask)
    return memory_top1_pallas(mem, q, mask, interpret=(impl == "interpret"))


def memory_top1_batch(mem: jax.Array, qs: jax.Array, mask: jax.Array,
                      impl: str | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Multi-query top-1: qs (B, E) against mem (C, E) in one store pass."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_top1_batch(mem, qs, mask)
    return memory_top1_batch_pallas(mem, qs, mask,
                                    interpret=(impl == "interpret"))


def memory_top1_padded(mem: jax.Array, q: jax.Array, mask: jax.Array,
                       required: int = MASK_VALID,
                       impl: str | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Zero-copy top-1 over a store already in kernel layout: mem (Cp, Ep),
    mask (Cp, 1) int32 bit plane, ``required`` the bit set a row must carry
    (see ``kernels.memory_topk``). The serving dispatch path."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_top1_padded(mem, q, mask, required)
    return memory_top1_padded_pallas(mem, q, mask, required=required,
                                     interpret=(impl == "interpret"))


def memory_top1_batch_padded(mem: jax.Array, qs: jax.Array, mask: jax.Array,
                             required: int = MASK_VALID,
                             impl: str | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Zero-copy multi-query top-1 over the padded kernel layout."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_top1_batch_padded(mem, qs, mask, required)
    return memory_top1_batch_padded_pallas(mem, qs, mask, required=required,
                                           interpret=(impl == "interpret"))


def memory_topk_padded(mem: jax.Array, q: jax.Array, mask: jax.Array,
                       k: int, required: int = MASK_VALID,
                       impl: str | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Zero-copy top-k over the padded kernel layout: (sims (k,),
    idx (k,)) sorted by (sim desc, row asc). The multi-guide serving
    dispatch path (``core.memory.query_topk``)."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_topk_padded(mem, q, mask, k, required)
    return memory_topk_padded_pallas(mem, q, mask, k=k, required=required,
                                     interpret=(impl == "interpret"))


def memory_topk_batch_padded(mem: jax.Array, qs: jax.Array, mask: jax.Array,
                             k: int, required: int = MASK_VALID,
                             impl: str | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Zero-copy multi-query top-k over the padded kernel layout:
    (sims (B, k), idx (B, k))."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.memory_topk_batch_padded(mem, qs, mask, k, required)
    return memory_topk_batch_padded_pallas(mem, qs, mask, k=k,
                                           required=required,
                                           interpret=(impl == "interpret"))


def ivf_route_padded(cent: jax.Array, q: jax.Array, cmask: jax.Array,
                     n_probe: int, required: int = MASK_VALID,
                     impl: str | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Level-1 centroid route over the padded centroid plane:
    (scores (n_probe,), cids (n_probe,)) sorted by (score desc, row asc).
    The IVF dispatch path (``core.memory_ivf``)."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.ivf_route_padded(cent, q, cmask, n_probe, required)
    return ivf_route_padded_pallas(cent, q, cmask, n_probe=n_probe,
                                   required=required,
                                   interpret=(impl == "interpret"))


def ivf_route_batch_padded(cent: jax.Array, qs: jax.Array, cmask: jax.Array,
                           n_probe: int, required: int = MASK_VALID,
                           impl: str | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """Multi-query level-1 centroid route: (scores (B, n_probe),
    cids (B, n_probe))."""
    impl = impl or default_impl()
    if impl == "ref":
        return ref.ivf_route_batch_padded(cent, qs, cmask, n_probe, required)
    return ivf_route_batch_padded_pallas(cent, qs, cmask, n_probe=n_probe,
                                         required=required,
                                         interpret=(impl == "interpret"))


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    impl: str | None = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale,
                                  interpret=(impl == "interpret"))


def decode_attention(q, k, v, cache_len, *, window=0, scale=None,
                     impl: str | None = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.decode_attention(q, k, v, cache_len, window=window,
                                    scale=scale)
    return decode_attention_pallas(q, k, v, cache_len, window=window,
                                   scale=scale,
                                   interpret=(impl == "interpret"))
