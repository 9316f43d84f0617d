#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic
mix, rate and per-layer metrics are found by name (``BENCHMARK.json``,
``bench/configs``, ``bench/mixes``, ``bench/cells``, ``bench/metrics``).
With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiled window. The last
line of standard output is the result as one JSON object; the numbers
that decided ``correct`` are the last lines of standard error. Exits
with 2, printing no result, where JAX finds no TPU or fewer chips than
the cell needs.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except (harness.NoChip, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
