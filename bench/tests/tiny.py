"""A cell cut to a size the CPU runs in seconds: the real mix and
configuration with every width and count shrunk, for the tests."""
from __future__ import annotations

import copy
import json

from bench import spec as S

TINY_TIER = {"num_layers": 1, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
             "vocab_size": 512, "param_dtype": "float32"}
TINY_SSM = {"num_layers": 1, "d_model": 64, "vocab_size": 512,
            "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16,
            "param_dtype": "float32"}
TINY_MIX = {"vocab": 512, "prompt_len": 32, "skill_part_len": 24,
            "skill_slice": 64, "guide_request_prefix": 8,
            "guide_request_len": 12, "known_skills": 64,
            "new_skill_pool": 4096, "warmup_s": 0.5,
            "calibration_skills": 32, "microbatch": 4}


def full_cell(workload: str) -> S.Cell:
    """The cell ``<config>.<traffic>`` from its files, whether or not
    ``BENCHMARK.json`` lists it (``mamba2-dsc33b.warm`` waits under Open
    questions in PERF.md, and its path is still tested)."""
    config, traffic = workload.split(".")
    bench = json.loads((S.ROOT / "BENCHMARK.json").read_text())
    return S.cell_from({"name": workload, "config": config,
                        "traffic": traffic, "chips": 1}, bench)


def tiny_cell(workload: str, rate: float = 40.0) -> S.Cell:
    cell = full_cell(workload)
    cfg = copy.deepcopy(cell.config)
    for tier in ("weak", "strong"):
        cut = TINY_SSM if cfg[tier]["family"] == "ssm" else TINY_TIER
        cfg[tier].update(cut)
    cfg["embedder"].update({"vocab_size": 512, "d_model": 32,
                            "num_layers": 2, "num_heads": 2, "d_ff": 64})
    cfg["store"]["capacity"] = 2048
    cfg["check_requests"] = 12
    # the limits of the embedder's numbers at this size: the program's
    # float32 embedder on the CPU reads about 5e-8 (largest component)
    # and 9e-9 (root mean square) from the reference, a bfloat16 one
    # about 1e-3 and 2.3e-4
    tiny = {"embed_gap": 1e-5, "store_gap": 1e-5, "embed_rms": 2e-6,
            "store_rms": 2e-6}
    cfg["limits"] = {k: tiny.get(k, v) for k, v in cfg["limits"].items()}
    mix = dict(cell.mix, **TINY_MIX)
    return S.Cell(name=workload, chips=1, config=cfg, mix=mix,
                  cell={"rate_rps": rate}, end_to_end=cell.end_to_end,
                  per_layer=cell.per_layer)
