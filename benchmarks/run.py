"""Benchmark orchestrator — one section per paper table/figure + the
roofline table and the memory-kernel microbench.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig7,...]

Emits ``name,us_per_call,derived`` CSV-style sections to stdout; detailed
per-benchmark CSV is printed inside each section.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks.common import print
from repro.cache import enable_compile_cache

SECTIONS = [
    ("fig4_rar_vs_baselines", "Fig 4: RAR vs baselines, professional law"),
    ("fig5_moral_scenarios", "Fig 5: moral scenarios domain"),
    ("fig6_hs_psychology", "Fig 6: high-school psychology domain"),
    ("fig7_guide_memory", "Fig 7: guide source per stage"),
    ("table1_generalization", "Table I: inter/intra-domain guides"),
    ("memory_bench", "Memory retrieval microbench"),
    ("rar_throughput", "RAR data plane: sequential vs microbatched"),
    ("roofline", "Roofline table from dry-run sweep"),
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    failures = []
    for mod_name, title in SECTIONS:
        if only and mod_name not in only:
            continue
        print(f"\n===== {mod_name}: {title} =====")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time() - t0:.1f}s")
        except Exception:
            failures.append(mod_name)
            print(f"# {mod_name} FAILED:\n{traceback.format_exc()}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
