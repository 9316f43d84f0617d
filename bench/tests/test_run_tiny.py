"""The harness end to end on the CPU at a tiny size, through its Python
API: the result line's schema, the realised mix, and the control's
readings."""
import io
import json
import math

import pytest

from bench import check as CK
from bench import harness
from bench.tests.tiny import tiny_cell

WORKLOADS = ["olmo1b-dsc33b.cold", "mamba2-dsc33b.warm"]
# strong calls per request the mix makes at this size: misses take a strong
# answer and (unless the tiny tiers happen to agree on an option)
# a guide generation, planted hard entries one strong answer
STRONG_SHARE = {"olmo1b-dsc33b.cold": (1.0, 2.0),
                "mamba2-dsc33b.warm": (0.05, 0.8)}


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.mark.parametrize("workload", WORKLOADS)
def test_tiny_run_result_line(workload):
    cell = tiny_cell(workload)
    res = harness.run_cell(workload, 3 << 33, 1.0, False, cell=cell,
                           require_tpu=False, log=lambda *a: None)
    out, err = io.StringIO(), io.StringIO()
    harness.print_result(res, err=err)
    line = json.loads(res and json.dumps(res))
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 40
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in line["metrics"].items():
        assert math.isfinite(m["value"]) and m["value"] > 0, name
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == set(cell.config["limits"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    lo, hi = STRONG_SHARE[workload]
    assert lo < line["metrics"]["strong_share"]["value"] < hi
    last = list(cell.config["limits"])[-1]
    assert err.getvalue().splitlines()[-1].startswith(f"check {last} ")


def test_control_reads_above_the_program():
    """The reference one precision down in the program's place (bfloat16
    embedder, fp8 tiers) goes through the same limits and comes out not
    correct, while the program (float32 on the CPU) is correct."""
    workload = WORKLOADS[0]
    cell = tiny_cell(workload)
    res = harness.run_cell(workload, 17, 1.0, False, cell=cell,
                           require_tpu=False, control=True,
                           log=lambda *a: None)
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    assert set(res["control"]["checks"]) == set(cell.config["limits"])
    r = res["readings"]
    assert r["control_bf16_embed_gap"] > 100 * max(r["embed_gap"], 1e-9)
    assert r["control_bf16_store_gap"] > 100 * max(r["store_gap"], 1e-9)
    for tier in ("weak", "strong"):
        for name in CK.CONTROL_NUMERICS["tiers"]:
            assert r[f"control_{name}_{tier}_gap"] >= 0.0
