"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control] [--faults half_batch] [--out FILE]

For each seed, in one process on the chip: the program's set-up calls
against the float32 reference (the lower readings), and, where asked, the
reference in bfloat16 put in the program's place (the control) and the
reference with a fault planted (upper readings). One JSON line per seed
and reading goes to standard output and, with ``--out``, to a file. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# The checkout root and the program, in place of this script's directory
# (whose module names must not shadow anything JAX imports).
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from benchmarks.chip import compare, harness

    cell = harness.load_cell(args.workload)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate.py: needs the cell's TPU chips", file=sys.stderr)
        return 3
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = open(args.out, "a") if args.out else None
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        su = harness.set_up(cell, seed)
        su.engine = su.call = None
        ref = harness.reference_of(cell, su)
        rows = [("program", su.prog)]
        if args.control:
            rows.append(("control_bf16", harness.reference_of(
                cell, su, dtype=jnp.bfloat16,
                precision=jax.lax.Precision.DEFAULT,
            )))
        for f in faults:
            rows.append((f, harness.reference_of(cell, su, fault=f)))
        for what, got in rows:
            line = json.dumps({
                "cell": cell.name, "seed": seed, "what": what,
                **compare.readings(got, ref, su.p0, su.snapshot_rounds,
                                   cell.limits),
                "losses": got["losses"][:6], "ref_losses": ref["losses"][:6],
            })
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
