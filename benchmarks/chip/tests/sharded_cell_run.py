"""Runs a tiny cohort-sharded cell through the harness on four virtual CPU
devices, once as the program is and once with the cross-chip sum of the
aggregation left out, and prints ``{"sound": bool, "no_exchange": bool}``
(each run's ``correct``) as its last line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmarks/chip/tests/sharded_cell_run.py

``test_chipbench_run.py`` starts it in a process of its own: the device
count is fixed when JAX starts.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

TRAFFIC = {
    "spec": {"partition": {"kind": "pathological_noniid",
                           "shards_per_client": 2},
             "fedavg": {"C": 1.0, "E": 2, "B": 10, "lr": 0.1},
             "strategy": {"kind": "fedavg"}, "codec": None,
             "execution": {"mesh_axes": "clients"}},
    "rounds_per_call": 1,
}


def drop_psum(stacked_params, weights, *, axis_name, interpret=False,
              accum_dtype=None, block_n=None):
    """The program's partial-sum aggregation without its ``psum``: each
    chip averages only its own clients."""
    import jax.numpy as jnp

    from repro.kernels.fedavg_agg import fedavg_aggregate
    from repro.utils.tree import tree_ravel_stacked, tree_unravel

    flat, spec = tree_ravel_stacked(stacked_params)
    w = jnp.asarray(weights, jnp.float32)
    partial = fedavg_aggregate(flat.astype(jnp.float32), w,
                               interpret=interpret, block_n=block_n)
    return tree_unravel(spec, partial / jnp.sum(w))


def main() -> int:
    import jax

    from benchmarks.chip import compare, harness
    from repro.kernels import ops

    assert len(jax.devices()) == 4, jax.devices()
    tmp = Path(tempfile.mkdtemp())
    try:
        bench_dir = tmp / "chip"
        shutil.copytree(harness.BENCH_DIR, bench_dir,
                        ignore=shutil.ignore_patterns("tests", "__pycache__"))
        cfg = json.loads((bench_dir / "configs/mnist_2nn.json").read_text())
        cfg.update(name="tiny_2nn", clients=8, examples_per_client=20)
        (bench_dir / "configs/tiny_2nn.json").write_text(json.dumps(cfg))
        shutil.copy(bench_dir / "configs/mnist_2nn.py",
                    bench_dir / "configs/tiny_2nn.py")
        (bench_dir / "traffic/tiny_sharded.json").write_text(json.dumps(TRAFFIC))
        (bench_dir / "limits/tiny_sharded.json").write_text(json.dumps(
            {k: {"limit": 1e-4} for k in compare.NUMBERS}))
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        bench["workloads"].append({"name": "tiny_sharded", "config": "tiny_2nn",
                                   "traffic": "tiny_sharded", "chips": 4,
                                   "why": "test"})
        cell = harness.load_cell("tiny_sharded", bench, bench_dir)
        out = {}
        for what in ("sound", "no_exchange"):
            if what == "no_exchange":
                ops.sharded_fedavg_aggregate = drop_psum
            res = harness.run_cell(cell, 2**31 + 3, 0.3, False, jax.devices(),
                                   time.perf_counter())
            out[what] = res["correct"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
