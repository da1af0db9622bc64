#!/usr/bin/env python3
"""One traced run of a cell that also reads the per-layer metrics waiting in
``pending_per_layer.json`` and records the fixtures
``tests/benchmark/test_bench_scope_join.py`` pins:

    python3 benchmark/record_spans.py --workload <cell> --seed <n> \
        --seconds <s> --window-s <w> --out-dir <dir>

The run goes through ``run.main`` and prints its validated line as any run
does.  Beside it: ``<dir>/<cell>_pending.json`` holds every pending metric
the readers under ``layers/`` gave for the whole traced window (and the
closure sum); ``<dir>/<cell>_spans.json.gz`` the first ``w`` seconds of the
window WHOLE (``scope_join.save_window``; the stock recorder keeps the first
4000 events, which hold no complete program); ``<dir>/<cell>_op_scopes.json.gz``
the program's published scopes of the programs that ran.  A builder's tool:
the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import contract, run, scope_join, trace_reduce  # noqa: E402


def pending_entries():
    with open(os.path.join(HERE, "pending_per_layer.json"), "r", encoding="utf-8") as fh:
        return json.load(fh)


def read_pending(facts, cell_name: str):
    """{metric: value or None} of the pending entries listed for the cell,
    plus the closure sum's two sides in seconds."""
    out = {}
    for m in pending_entries():
        if cell_name in m["workloads"]:
            mod = contract.load_module(os.path.join(HERE, "layers", m["name"] + ".py"),
                                       "benchmark_layer_" + m["name"])
            out[m["name"]] = mod.read(facts)
    both = scope_join.closure(facts)
    if both is not None:
        out["closure_layers_s"], out["closure_program_s"] = both
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--window-s", type=float, required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    stem = os.path.join(args.out_dir, args.workload)
    os.makedirs(args.out_dir, exist_ok=True)

    def save(raw, path):
        scope_join.save_window(raw, path, args.window_s)

    reduce = run._traced_metrics

    def traced_metrics(cell, manifest, facts, *rest):
        result = reduce(cell, manifest, facts, *rest)
        try:
            values = read_pending(facts, cell.name)
            values["scoped_seconds"] = scope_join.scoped_seconds(facts)
        except scope_join.JoinError as e:  # the fixtures are still worth having
            values = {"error": str(e)}
        with open(stem + "_pending.json", "w", encoding="utf-8") as fh:
            json.dump(values, fh, indent=1)
        run.log("pending per-layer metrics: " + json.dumps(values))
        if facts["trace"].devices:
            scope_join.save_scopes(facts["trace"], stem + "_op_scopes.json.gz")
        return result

    trace_reduce.save_recorded = save
    run._traced_metrics = traced_metrics
    run.TRACE_DIR = os.path.join(args.out_dir, ".bench_trace")  # this run's own
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "1",
            "--keep-trace", stem + "_spans.json.gz"]
    return run.main(argv + (["--rehearse"] if args.rehearse else []))


if __name__ == "__main__":
    sys.exit(main())
