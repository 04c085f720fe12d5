"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; the harness
(``harness.py``) finds its configuration, traffic and limits by name. The
run needs as many TPU chips as the cell asks for: with no TPU, or too few,
it exits non-zero and prints no result. Otherwise the last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared with its limit); the same checks are the
last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# The checkout root and the program, in place of this script's directory
# (whose module names must not shadow anything JAX imports).
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    # libtpu's logs go under the run's own temporary directory.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX found {devices[0].platform}); this "
              "benchmark never falls back to another platform",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3

    harness.log(f"jax_init_s={time.perf_counter() - T_START:.3f} "
                f"devices={len(devices)} kind={devices[0].device_kind}")
    from repro.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    # Every program, however quick to compile, goes to the persistent
    # cache, so a run after the first in a checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"cell={cell.name} chips={cell.chips} cache={cache_dir}")
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices[:cell.chips], T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
