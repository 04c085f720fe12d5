"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix, limit and metric reader is found by name; a missing one is
refused; the entry point refuses to run without a TPU."""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import compare, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_and_metric_loads():
    harness.validate(BENCH)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell, BENCH)
    assert c.chips in (1, 4)
    named = [k for k in compare.NUMBERS if k in c.limits]
    assert named, "every cell compares at least one number"
    for key in named:
        lim = c.limits[key]["limit"]
        assert math.isfinite(lim) and lim > 0
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert c.rounds_per_call >= 1
    r = c.spec_overrides.get("execution", {}).get("rounds_per_step") or 1
    assert c.rounds_per_call % r == 0
    spec = harness.build_spec(c, 123)
    assert spec.model.kind == c.config["model"]["kind"]
    assert spec.fedavg.seed == 123


def test_metric_workloads_name_existing_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    bad = dict(BENCH, per_layer=BENCH["per_layer"] + [
        dict(BENCH["per_layer"][0], name="x", workloads=["no_such_cell"])])
    with pytest.raises(KeyError):
        harness.validate(bad)


def test_benchmark_names_and_units_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):  # the cell reports what it moves
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (ROOT / c["file"]).with_suffix(".py").is_file()
    lines = [e["why"] for e in BENCH["configs"] + BENCH["workloads"]]
    lines += [m["layer"] for m in BENCH["per_layer"]]
    lines += [c["source"] for c in BENCH["configs"]] + BENCH["command"]
    for text in lines:
        assert 1 <= len(text) <= 200 and not re.search(r"[\t\n]", text), text


def test_a_missing_cell_or_file_is_refused(tmp_path):
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell", BENCH)
    bench_dir = tmp_path / "chip"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    first = BENCH["workloads"][0]
    (bench_dir / "traffic" / f"{first['traffic']}.json").unlink()
    with pytest.raises(FileNotFoundError):
        harness.load_cell(first["name"], BENCH, bench_dir)
    with pytest.raises(FileNotFoundError):
        harness.validate(BENCH, bench_dir)


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new cell needs a traffic file, a limits file and a BENCHMARK.json
    entry: the harness is not edited."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    traffic = {"spec": {"partition": {"kind": "iid"},
                        "fedavg": {"C": 0.2, "E": 2, "B": 20, "lr": 0.05},
                        "strategy": {"kind": "fedavg"}, "codec": None,
                        "execution": {}},
               "rounds_per_call": 2}
    (bench_dir / "traffic" / "throwaway.json").write_text(json.dumps(traffic))
    shutil.copy(bench_dir / "limits" / f"{BENCH['workloads'][0]['name']}.json",
                bench_dir / "limits" / "throwaway_cell.json")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "throwaway_cell", "config": "mnist_2nn",
        "traffic": "throwaway", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("round_s", "device_idle_share"):
            m["workloads"].append("throwaway_cell")
    harness.validate(bench, bench_dir)
    cell = harness.load_cell("throwaway_cell", bench, bench_dir)
    assert cell.rounds_per_call == 2
    assert harness.build_spec(cell, 7).fedavg.B == 20
    assert {m["name"] for m in cell.end_to_end} == {"round_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["device_idle_share"]


@pytest.mark.parametrize("change, error", [
    ({"n_topics": None}, KeyError),
    ({"seq_len": None}, KeyError),
    ({"reference_clients_per_block": 0}, ValueError),
    ({"reference_clients_per_block": 1.5}, ValueError),
], ids=["no_n_topics", "no_seq_len", "block_0", "block_1.5"])
def test_a_token_config_is_checked_before_a_run(tmp_path, change, error):
    """A configuration of token sequences must name its population's keys
    and a whole number of clients for each reference block."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = {"name": "tokens", "model": {"kind": "char_lstm",
                                       "kwargs": {"vocab_size": 40}},
           "population": "tokens", "vocab_size": 40, "seq_len": 16,
           "clients": 4, "examples_per_client": 8, "n_topics": 4,
           "reference_clients_per_block": 2}
    bench = json.loads(json.dumps(BENCH))
    first = bench["workloads"][0]
    bench["workloads"].append(dict(first, name="tokens_cell",
                                   config="tokens"))
    shutil.copy(bench_dir / "limits" / f"{first['name']}.json",
                bench_dir / "limits" / "tokens_cell.json")
    shutil.copy(bench_dir / "configs" / f"{first['config']}.py",
                bench_dir / "configs" / "tokens.py")
    (bench_dir / "configs" / "tokens.json").write_text(json.dumps(cfg))
    harness.validate(bench, bench_dir)
    broken = {k: v for k, v in {**cfg, **change}.items() if v is not None}
    (bench_dir / "configs" / "tokens.json").write_text(json.dumps(broken))
    with pytest.raises(error):
        harness.validate(bench, bench_dir)


def _run(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_run_refuses_without_a_tpu():
    cell = BENCH["workloads"][0]["name"]
    r = _run(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "1",
              "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
    assert "no TPU" in r.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directory has no program to measure: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = BENCH["workloads"][0]["name"]
    r = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
