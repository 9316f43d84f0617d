"""``chip_smoke.py`` off the chip: it refuses a CPU backend without printing
a result, and its retrieval check passes on a small store here."""
import importlib.util
from pathlib import Path

import jax

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_cpu_backend(capsys, monkeypatch, tmp_path):
    # a set cache directory leaves this process's JAX config untouched
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert _load().main([]) == 1
    assert jax.config.jax_compilation_cache_dir == before
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_retrieval_check_on_a_small_store(capsys):
    _load().phase_retrieval(jax, C=4096, E=384)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2 and all("rows match" in ln for ln in lines)
