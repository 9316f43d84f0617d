"""The command refuses to run without a TPU, and without the program;
``BENCHMARK.json`` names only files that exist."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec as S

CMD = ["--workload", "olmo1b-dsc33b.cold", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *CMD], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_backend():
    p = _run(S.ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(S.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_names_existing_files():
    bench = json.loads((S.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (S.ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        cell = S.load_cell(w["name"])
        assert cell.cell["rate_rps"] > 0
        assert cell.per_layer and cell.end_to_end
    for m in bench["per_layer"]:
        assert callable(S.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


@pytest.mark.parametrize("kind", ["TPU v5 lite", "no such chip"])
def test_peaks_table(kind):
    if kind == "TPU v5 lite":
        assert S.peaks(kind)["bf16_flops"] == 197e12
    else:
        with pytest.raises(KeyError):
            S.peaks(kind)
