#!/usr/bin/env python3
"""A builder's tool: the numbers `correct` compares, on many seeds, in ONE
process (set-up on the chip is long; the contract allows it):

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 [--control-seeds 2] [--fault <name>]

Each seed is one whole `run.py` run with a one-second window (the trees
compared are the warm-up's, grown at the cell's full size); the first
``--control-seeds`` of them also read the lower-precision control.  With
``--fault`` every run has that fault of ``faults.py`` planted.  Every run
prints its line; `setup_s` and `memory_peak_bytes` of all but the first are
the process's, not a run's, and mean nothing here.  Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import faults, run

    worst = 0
    for i, seed in enumerate(s for s in args.seeds.split(",") if s):
        argv_run = ["--workload", args.workload, "--seed", seed, "--seconds", "1",
                    "--trace", "0"]
        if i < args.control_seeds:
            argv_run += ["--control", "bfloat16"]
        if args.rehearse:
            argv_run.append("--rehearse")
        planted = faults.FAULTS[args.fault]() if args.fault else contextlib.nullcontext()
        with planted:
            worst = max(worst, run.main(argv_run))
    return worst


if __name__ == "__main__":
    sys.exit(main())
