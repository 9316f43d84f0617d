"""Fast checks of the benchmark's own arithmetic: the traffic generator,
the FLOP and byte counts, the weight trees, and the trace reduction on
hand-made planes."""
import dataclasses

import numpy as np
import pytest

from bench import flops as F
from bench import spec as S
from bench import trace as TR
from bench import traffic as TF
from bench.tests.tiny import full_cell, tiny_cell

WORKLOADS = ["olmo1b-dsc33b.cold", "mamba2-dsc33b.warm"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_is_deterministic_per_seed(workload):
    mix = tiny_cell(workload).mix
    a = TF.generate(mix, 40.0, 2.0, 3 << 40, 8)
    b = TF.generate(mix, 40.0, 2.0, 3 << 40, 8)
    c = TF.generate(mix, 40.0, 2.0, 5, 8)
    for x, y in zip(a.window, b.window):
        assert x.t == y.t and x.skill == y.skill and x.kind == y.kind
        assert np.array_equal(x.prompt, y.prompt)
        assert np.array_equal(x.greq, y.greq)
    assert np.array_equal(a.known_kind, b.known_kind)
    # another seed: other content, the same work at the same instants
    assert len(a.window) == len(c.window) == 80
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.window, c.window))
    for x, y in zip(a.window + a.warmup, c.window + c.warmup):
        assert x.t == y.t
        assert (x.kind == "new") == (y.kind == "new")
        if x.kind != "new":
            assert x.kind == y.kind


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_shares_and_shapes(workload):
    mix = tiny_cell(workload).mix
    tr = TF.generate(mix, 40.0, 5.0, 11, 8)
    n = len(tr.window)
    known = [r for r in tr.window if r.kind != "new"]
    assert len(known) == round(mix["known_share"] * n)
    new_skills = [r.skill for r in tr.warmup + tr.window if r.kind == "new"]
    assert len(set(new_skills)) == len(new_skills)        # each seen once
    counts = np.bincount(tr.known_kind, minlength=3) / len(tr.known_kind)
    want = [mix["known_kinds"][k] for k in TF.KINDS]
    np.testing.assert_allclose(counts, want, atol=1.0 / len(tr.known_kind))
    for r in tr.window:
        assert r.prompt.shape == (mix["prompt_len"],)
        assert r.prompt[0] == TF.BOS and r.prompt[-1] == TF.ANS
        assert r.greq.shape == (mix["guide_request_len"],)
        assert r.prompt.min() >= 1 and r.prompt.max() < mix["vocab"]
        assert 0 <= r.t < 5.0
    # requests of one skill share the whole skill part
    by_skill = {}
    for r in tr.window:
        by_skill.setdefault(r.skill, []).append(r.prompt)
    P = mix["skill_part_len"]
    for ps in by_skill.values():
        assert all(np.array_equal(p[:P], ps[0][:P]) for p in ps)


def test_flops_hand_counts():
    dense = {"family": "dense", "num_layers": 1, "d_model": 4,
             "num_heads": 2, "num_kv_heads": 1, "head_dim": 2, "d_ff": 8,
             "vocab_size": 10}
    assert F.dense_layer_params(dense) == 144
    # 2*144*3 + 4*2*2*(1+2+3) + 2*10*4
    assert F.tier_flops(dense, 0, 3, 1) == 1040
    # the prefill, then one decode step at position 3
    assert F.generate_flops(dense, 3, 2) == 1040 + 288 + 64 + 80
    ssm = {"family": "ssm", "num_layers": 1, "d_model": 4, "ssm_expand": 2,
           "ssm_head_dim": 4, "ssm_state": 3, "ssm_groups": 1, "d_conv": 4,
           "vocab_size": 10}
    assert F.ssm_layer_params(ssm) == 128
    # (2*128 + 4*2*4*3 + 2*4*14) * 5 + 2*10*4
    assert F.tier_flops(ssm, 0, 5, 1) == 2400
    emb = {"d_model": 4, "d_ff": 8, "num_layers": 1, "embed_dim": 3}
    assert F.embedder_flops(emb, 5) == 2024
    assert F.topk_bytes(10, 4, 2) == 232
    assert F.topk_flops(10, 4, 2) == 160


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("tier", ["weak", "strong"])
def test_layer_params_match_program(workload, tier):
    import sys
    sys.path.insert(0, str(S.ROOT / "src"))
    from repro.models.config import ModelConfig
    cfg = full_cell(workload).config[tier]
    mc = ModelConfig(**cfg)
    embed = cfg["vocab_size"] * cfg["d_model"] * \
        (1 if cfg["tie_embeddings"] else 2)
    per = F.ssm_layer_params(cfg) if cfg["family"] == "ssm" else \
        F.dense_layer_params(cfg)
    assert mc.param_count() == embed + cfg["num_layers"] * per


@pytest.mark.parametrize("workload", WORKLOADS)
def test_weight_trees_match_program_layout(workload):
    import sys
    sys.path.insert(0, str(S.ROOT / "src"))
    import jax
    from repro.core import embedder as E
    from repro.models.config import ModelConfig
    from repro.models.model import init_params

    from bench import weights as W
    cell = tiny_cell(workload)
    key = jax.random.PRNGKey(0)
    for tier in ("weak", "strong"):
        cfg = cell.config[tier]
        ours = jax.eval_shape(lambda k: W.make_tier(cfg, k), key)
        theirs = jax.eval_shape(
            lambda k: init_params(ModelConfig(**cfg), k), key)
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
    ecfg = cell.config["embedder"]
    ours = jax.eval_shape(lambda k: W.make_embedder(ecfg, k), key)
    theirs = jax.eval_shape(
        lambda k: E.init_params(E.EmbedderConfig(**ecfg), k), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


def test_trace_reduction_by_hand():
    host = _Plane("/host:CPU", [_Line("main", [
        _Ev("bench.window", 0, 1000),
        _Ev("bench.tier.weak", 100, 300),
        _Ev("bench.embed", 600, 100)])])
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("fusion.1", 150, 100),          # 150-250
        _Ev("fusion.2", 200, 100),          # overlaps: union 150-300
        _Ev("my_topk_kernel", 700, 50),     # 700-750
        _Ev("fusion.3", 990, 100)])])       # clipped to 990-1000
    s = TR.reduce_planes([host, dev], {"topk": "topk"})
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((150 + 50 + 10) * 1e-9)
    assert s.kernel_events["topk"] == [pytest.approx(50e-9)]
    assert s.op_seconds["fusion"] == pytest.approx(210e-9)
    # gaps 0-150, 300-700 and 750-990, cut where host spans start or end:
    # 100-150 and 300-400 in the tier call, 600-700 in the embed call
    assert s.idle_by_host["no_request"] == pytest.approx(540e-9)
    assert s.idle_by_host["bench.tier.weak"] == pytest.approx(150e-9)
    assert s.idle_by_host["bench.embed"] == pytest.approx(100e-9)
    b = TR.breakdown(s)
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0][0] == "no_request"
    assert len(b["idle_gaps"]) == 3


def test_op_names():
    assert TR.op_name("%fusion.12 = bf16[2]{0} fusion(%a)") == "fusion"
    assert TR.op_name("%copy-start = (f32[4]) copy-start(%x)") == \
        "copy-start"
    assert TR.op_name(
        "%memory_topk_batch_padded_pallas.1 = (f32[1,128]) custom-call()") \
        == "memory_topk_batch_padded_pallas"


def test_trace_idle_while_requests_in_flight():
    """Idle time with requests in the program and no probe span open is
    the serve path's own host work; idle time with none is no_request."""
    host = _Plane("/host:CPU", [_Line("main", [
        _Ev("bench.window", 1000, 1000),
        _Ev("bench.tier.weak", 1100, 100)])])
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("fusion.1", 1150, 50), _Ev("fusion.2", 1700, 100)])])
    # requests held from 1050 ns to 1800 ns of the trace's clock
    s = TR.reduce_planes([host, dev], {}, in_flight=[(50e-9, 800e-9)])
    idle = s.idle_by_host
    assert idle["no_request"] == pytest.approx((50 + 200) * 1e-9)
    assert idle["bench.tier.weak"] == pytest.approx(50e-9)
    assert idle["bench.serve"] == pytest.approx((50 + 500) * 1e-9)
