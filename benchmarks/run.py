"""Benchmark harness entrypoint: one function per paper table/figure plus
the kernel microbenches and the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,fig1] \
        [--json BENCH_pr4.json]

Prints ``name,us_per_call,derived`` CSV lines (# lines are commentary).
``--json PATH`` additionally writes every emitted row as machine-readable
JSON ({rows, suites, failed, quick}) so the perf trajectory is tracked
across PRs — CI smokes the superstep suite this way into BENCH_<pr>.json.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

from benchmarks import common
from benchmarks import (
    async_rounds,
    compression,
    fig1_averaging,
    gossip,
    fig3_large_E,
    kernels_bench,
    roofline_report,
    round_engine,
    shakespeare_lstm,
    table1_client_fraction,
    table2_local_computation,
    table3_cifar,
)
from repro.utils.compile_cache import use_compile_cache

SUITES = {
    "table1": table1_client_fraction.main,
    "table2": table2_local_computation.main,
    "table3": table3_cifar.main,
    "fig1": fig1_averaging.main,
    "fig3": fig3_large_E.main,
    "shakespeare": shakespeare_lstm.main,
    "kernels": kernels_bench.main,
    "kernels_wire": kernels_bench.wire_path,
    "roofline": roofline_report.main,
    "roofline_wire": roofline_report.wire_path,
    "round_engine": round_engine.main,
    "round_engine_scaling": round_engine.scaling,
    "round_engine_superstep": round_engine.superstep,
    "round_engine_strategy": round_engine.strategy_overhead,
    "round_engine_async": async_rounds.main,
    "gossip": gossip.main,
    "compression": compression.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale budgets")
    ap.add_argument("--only", default=None, help="comma list of suite names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all emitted rows as machine-readable JSON "
                         "(e.g. BENCH_pr4.json)")
    args = ap.parse_args()
    use_compile_cache()
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            SUITES[name](quick=not args.full)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    if args.json:
        Path(args.json).write_text(json.dumps({
            "rows": common.ROWS,
            "suites": names,
            "failed": failed,
            "quick": not args.full,
        }, indent=2) + "\n")
        print(f"# wrote {len(common.ROWS)} rows to {args.json}")
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
