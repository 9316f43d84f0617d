#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: for each seed, one short
run of a cell at its own load, printing the program's readings and,
on the first ``--control-seeds`` seeds, the control's (the reference in
a lower precision put in the program's place: int8 and fp8 tiers, a
bfloat16 embedder) with its verdict under the same limits, one JSON line
per seed. All seeds run in one process, so set-up compiles once.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds 1 2 3 4 --control-seeds 2
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds (all)")
    args = ap.parse_args(argv)
    n_control = len(args.seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(args.seeds):
        t = time.monotonic()
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   control=i < n_control)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        line = {"seed": seed, "correct": res["correct"],
                "failed": res["failed"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "checks": {k: c["value"] for k, c in res["checks"].items()},
                "seconds": time.monotonic() - t}
        if "control" in res:
            line["control"] = res["control"]
        line["readings"] = res["readings"]
        print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
