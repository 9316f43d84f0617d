"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root, and under this directory ``configs/<config>.json``,
``mixes/<traffic>.json``, ``cells/<workload>.json`` and
``metrics/<metric>.py``. Adding a cell, a mix or a metric adds files
and entries; no file here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    cell: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """Everything one run of ``workload`` reads, by name."""
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    return cell_from(cells[workload], bench)


def cell_from(w: dict, bench: dict) -> Cell:
    """The cell that the ``workloads`` entry ``w`` names, with the
    metrics of ``bench`` that apply to it."""
    workload = w["name"]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load(BENCH / "configs" / f"{w['config']}.json"),
        mix=_load(BENCH / "mixes" / f"{w['traffic']}.json"),
        cell=_load(BENCH / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind`` from ``peaks.json``; a
    kind that is not in the table is an error, not a default."""
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
