#!/usr/bin/env python3
"""The knee sweep of a cell: short runs at several offered rates, each
printing completions, latency and backlog as one JSON line. The knee is
the highest rate at which completions keep up with arrivals over the
window (no backlog growing through it); a cell's rate is fixed at about
four fifths of it in ``cells/<workload>.json``.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 5 10 20 \
        --seeds 1 2

The comparison after each run reads a small sample (``--check-requests``):
a sweep looks for the knee, not for the limits of ``correct``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402
from bench import spec as S  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--check-requests", type=int, default=16)
    args = ap.parse_args(argv)
    base = S.load_cell(args.workload)
    config = dict(base.config, check_requests=args.check_requests)
    for seed in args.seeds:
        for rate in args.rates:
            cell = dataclasses.replace(base, config=config,
                                       cell=dict(base.cell, rate_rps=rate))
            lines = []
            try:
                res = harness.run_cell(args.workload, seed, args.seconds,
                                       False, cell=cell, log=lines.append)
            except harness.NoChip as e:
                print(f"sweep: {e}", file=sys.stderr)
                return 2
            m = {k: v["value"] for k, v in res["metrics"].items()}
            print(json.dumps({"rate": rate, "seed": seed,
                              "attempted": res["attempted"],
                              "failed": res["failed"],
                              "correct": res["correct"], **m,
                              "log": lines}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
