"""A run with the timed path broken underneath comes out not correct:
a token altered where a tier produces it, a store commit that leaves the
store unchanged, and half of each microbatch never answered."""
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import tiny_cell


def _alter_tokens(tier):
    def hook(b):
        engine = getattr(b, tier).inner.engine
        real = engine.generate_bucketed

        def altered(prompts, max_new):
            out = np.asarray(real(prompts, max_new)).copy()
            out[0, -1] = (out[0, -1] + 1) % engine.cfg.vocab_size
            engine.last_out = out
            return out
        engine.generate_bucketed = altered
    return hook


def _unchanged_store(b):
    stream = b.fabric.commit_stream

    def apply(state):
        stream.buffer.take_ops()
        return state
    stream.apply = apply


def _half_batch(b):
    fabric = b.proxy.fabric
    real = fabric.submit

    def submit(prompts, guide_requests, keys=None, embs=None, replica=None):
        h = max(1, len(prompts) // 2) if len(prompts) > 1 else 1
        if len(prompts) > 1:
            prompts, guide_requests = prompts[:h], guide_requests[:h]
            keys = keys[:h] if keys is not None else None
        return real(prompts, guide_requests, keys=keys, embs=embs,
                    replica=replica)
    fabric.submit = submit


FAULTS = {"weak_token": _alter_tokens("weak"),
          "strong_token": _alter_tokens("strong"),
          "store_unchanged": _unchanged_store,
          "half_batch": _half_batch}


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(fault):
    workload = "olmo1b-dsc33b.cold"
    cell = tiny_cell(workload)
    cell.config["check_requests"] = 40
    harness.WAIT_AFTER_S, saved = 3.0, harness.WAIT_AFTER_S
    try:
        res = harness.run_cell(workload, 29, 1.0, False, cell=cell,
                               require_tpu=False, hook=FAULTS[fault],
                               log=lambda *a: None)
    finally:
        harness.WAIT_AFTER_S = saved
    assert res["correct"] is False
