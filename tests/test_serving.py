"""Serving engine: greedy decode correctness vs. repeated teacher forcing,
jit cache behaviour, call accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import forward, init_params
from repro.serving.engine import ServingEngine, greedy_generate


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_smoke("olmo-1b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def greedy_reference(cfg, params, tokens, max_new):
    """Greedy decode via repeated full forward passes (no cache)."""
    cur = tokens
    out = []
    for _ in range(max_new):
        batch = {"tokens": cur, "labels": jnp.zeros_like(cur)}
        logits, _ = forward(cfg, params, batch)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        out.append(nxt)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_greedy_generate_matches_reference(small_model, rng):
    cfg, params = small_model
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 12)), jnp.int32)
    got = greedy_generate(cfg, params, {"tokens": tokens}, max_new=5)
    want = greedy_reference(cfg, params, tokens, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_jit_cache_and_accounting(small_model, rng):
    cfg, params = small_model
    engine = ServingEngine(cfg, params)
    t1 = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 8)), jnp.int32)
    engine.generate({"tokens": t1}, max_new=2)
    assert engine.calls == 4
    engine.generate({"tokens": t1}, max_new=2)
    assert engine.calls == 8
    assert len(engine._jitted) == 1            # same shape → cached
    t2 = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)), jnp.int32)
    engine.generate({"tokens": t2}, max_new=2)
    assert len(engine._jitted) == 2
    # a forward pass: 2 N per token, a third of the training count
    assert engine.flops_spent == pytest.approx(
        engine.tokens_processed * 2 * cfg.active_param_count())
    assert engine.flops_spent == pytest.approx(
        engine.tokens_processed * cfg.flops_per_token() / 3)


def test_generate_bucketed_matches_per_prompt(small_model, rng):
    """Mixed-length prompts through the bucketed path: same outputs as
    one-by-one serving, call accounting counts real rows only, and jit
    entries are shared across repeated mixed-length traffic."""
    cfg, params = small_model
    engine = ServingEngine(cfg, params)
    prompts = [np.asarray(rng.integers(1, cfg.vocab_size, L), np.int32)
               for L in (8, 12, 8, 12, 12, 9)]
    got = engine.generate_bucketed(prompts, max_new=3)
    assert engine.calls == len(prompts)
    for p, row in zip(prompts, got):
        one = np.asarray(engine.generate(
            {"tokens": jnp.asarray(p[None])}, max_new=3))[0]
        np.testing.assert_array_equal(row, one)
    # a second mixed batch with the same lengths but different group sizes
    # must not add compile entries beyond the (bucket, length) grid
    n_entries = len(engine._jitted)
    more = [np.asarray(rng.integers(1, cfg.vocab_size, L), np.int32)
            for L in (8, 8, 12, 12, 12, 9)]
    engine.generate_bucketed(more, max_new=3)
    assert len(engine._jitted) == n_entries       # all buckets reused


def test_ssm_generate_runs(rng):
    """State-carrying family through the same engine API."""
    cfg = configs.get_smoke("mamba2-2.7b")
    params = init_params(cfg, jax.random.PRNGKey(2))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)), jnp.int32)
    out = greedy_generate(cfg, params, {"tokens": tokens}, max_new=4)
    assert out.shape == (2, 4)
    ref = greedy_reference(cfg, params, tokens, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_hybrid_generate_runs(rng):
    cfg = configs.get_smoke("recurrentgemma-2b")
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)), jnp.int32)
    out = greedy_generate(cfg, params, {"tokens": tokens}, max_new=4)
    ref = greedy_reference(cfg, params, tokens, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
