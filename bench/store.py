"""The guide store planted full before a run, made on the device in one
jitted call.

Every slot is valid and the ring pointer stands at capacity, so the
first commit of a run overwrites slot 0. The known skills sit in the
top ``K`` slots, out of reach of the run's commits (a run commits far
fewer than ``C - K`` entries); the rest are seeded unit vectors, half
of them carrying a random guide. Planted entries are stamped at logical
time ``PLANTED_AT``, later than any request of a run, so a planted hard
entry stays hard for the whole run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

PLANTED_AT = 1 << 30


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _plant(C: int, E: int, G: int, Cp: int, Ep: int, first: int, vocab: int,
           key, known_emb, known_guides, known_has_guide, known_hard):
    K = known_emb.shape[0]
    ke, kb, kg = jax.random.split(key, 3)
    emb = jax.random.normal(ke, (Cp, Ep), jnp.float32)
    emb = jnp.where(jnp.arange(Ep)[None, :] < E, emb, 0.0)
    emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
    emb = jnp.where(jnp.arange(Cp)[:, None] < C, emb, 0.0)
    emb = jax.lax.dynamic_update_slice(
        emb, jnp.pad(known_emb.astype(jnp.float32), ((0, 0), (0, Ep - E))),
        (C - K, 0))
    filler_guide = jax.random.bernoulli(kb, 0.5, (C,))
    has_guide = filler_guide.at[C - K:].set(known_has_guide)
    toks = jax.random.randint(kg, (C, 2), first, vocab, jnp.int32)
    guide = jnp.zeros((C, G), jnp.int32)
    guide = guide.at[:, 0].set(5).at[:, 1:3].set(toks).at[:, 3].set(6)
    guide = jnp.where(filler_guide[:, None], guide, 0)
    guide = guide.at[C - K:].set(known_guides)
    hard = jnp.zeros((C,), bool).at[C - K:].set(known_hard)
    added_at = jnp.zeros((C,), jnp.int32).at[C - K:].set(PLANTED_AT)
    mask_rows = 1 + 2 * has_guide.astype(jnp.int32)
    mask = jnp.zeros((Cp, 1), jnp.int32).at[:C, 0].set(mask_rows)
    return emb, mask, guide, hard, added_at


def plant(mem_cfg: dict, key, known_emb, known_guides, known_has_guide,
          known_hard, *, first: int, vocab: int, padded_rows, padded_lanes):
    """(emb (Cp, Ep), mask (Cp, 1), guide (C, G), hard (C,), added_at (C,))
    in the store's kernel layout; ``padded_rows``/``padded_lanes`` are the
    program's layout rule."""
    C, E, G = mem_cfg["capacity"], mem_cfg["embed_dim"], mem_cfg["guide_len"]
    return _plant(C, E, G, padded_rows(C), padded_lanes(E), first, vocab,
                  key, known_emb, known_guides, known_has_guide, known_hard)
