"""The DeepSeek-V2-Lite share's cell, its files and a whole run of it at a
tiny size on the CPU.

The tiny cell is added by files alone, as ``test_chipbench_run.py`` adds
its cells: the share's reference (``configs/deepseek_v2_lite_share8.py``)
beside a configuration of the same keys at a DeepSeek shape cut to CPU size
(hidden 64, 4 heads, kv_lora 16, rope 8, 16 routed experts of which 4 are
held, top 3, 1 shared, 1 dense + 2 MoE layers, vocabulary 128, sequences of
32), the program's ``transformer`` kind with the same overrides, 4 silos
run one at a time. Its limits are this size's own: the program reads under
3e-5 against the float32 reference on the CPU, the bfloat16 control above
1e-3.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import compare, harness  # noqa: E402

CELL = "dsv2lite_silo4_seq2048"
CONFIG = "deepseek_v2_lite_share8"
TINY_LIMIT = 1e-4
PROGRAM_AT_MOST = 3e-5

TINY_OVERRIDES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 16, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "vocab_size": 128, "seq_len": 32,
    "examples_per_client": 8, "held_experts": [4, 4],
    "published": {"num_hidden_layers": 27, "n_routed_experts": 16,
                  "vocab_size": 102400},
}
TINY_MODEL_KWARGS = {
    "preset": "deepseek_v2_lite_16b", "n_layers": 3, "vocab_size": 128,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
    "param_dtype": "float32", "compute_dtype": "float32", "ce_chunk": 0,
    "mla": {"kv_lora_rank": 16, "qk_rope_dim": 8, "qk_nope_dim": 16,
            "v_head_dim": 16},
    "moe": {"n_experts": 16, "topk": 3, "n_shared_experts": 1, "d_ff": 32,
            "dense_d_ff": 96, "n_held": 4, "held_first": 4},
}
TINY_TRAFFIC = {
    "spec": {"partition": {"kind": "pathological_noniid",
                           "shards_per_client": 1},
             "fedavg": {"C": 1.0, "E": 1, "B": 4, "lr": 0.05},
             "strategy": {"kind": "fedavg"}, "codec": None,
             "execution": {"clients_per_chunk": 1}},
    "rounds_per_call": 1,
}


def tiny_config() -> dict:
    cfg = json.loads((harness.BENCH_DIR / "configs" / f"{CONFIG}.json")
                     .read_text())
    cfg.update(TINY_OVERRIDES, name="tiny_dsv2")
    cfg["model"] = {"kind": "transformer", "kwargs": TINY_MODEL_KWARGS}
    return cfg


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    bench_dir = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    configs = bench_dir / "configs"
    (configs / "tiny_dsv2.json").write_text(json.dumps(tiny_config()))
    shutil.copy(configs / f"{CONFIG}.py", configs / "tiny_dsv2.py")
    (bench_dir / "traffic" / "tiny_dsv2.json").write_text(
        json.dumps(TINY_TRAFFIC))
    limits = {k: {"limit": TINY_LIMIT} for k in compare.NUMBERS}
    (bench_dir / "limits" / "tiny_dsv2.json").write_text(json.dumps(limits))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_dsv2", "config": "tiny_dsv2",
                               "traffic": "tiny_dsv2", "chips": 1,
                               "why": "test"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "round_s":
            metric["workloads"].append("tiny_dsv2")
    harness.validate(bench, bench_dir)
    return harness.load_cell("tiny_dsv2", bench, bench_dir)


def test_tiny_share_runs_correct_through_the_harness(tiny):
    import jax

    out = harness.run_cell(tiny, 2**31 + 21, 0.3, False, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    for key in compare.NUMBERS:
        assert out["checks"][key]["value"] < PROGRAM_AT_MOST


def test_tiny_share_control_in_bfloat16_is_not_correct(tiny):
    import jax
    import jax.numpy as jnp

    su = harness.set_up(tiny, 2**31 + 22)
    su.engine = su.call = None
    ref = harness.reference_of(tiny, su)
    control = harness.reference_of(tiny, su, dtype=jnp.bfloat16,
                                   precision=jax.lax.Precision.DEFAULT)
    values = compare.readings(control, ref, su.p0, su.snapshot_rounds,
                              tiny.limits)
    ok, checks = compare.judge(values, {k: TINY_LIMIT for k in values})
    assert not ok, checks


def test_share_cell_entries():
    """The new configuration, cell and metrics are as the benchmark's rules
    ask: the cut's keys listed and stated, the catalog's numbers kept
    elsewhere, each metric applied to the cell that reports it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert conf["reduced"] == cfg["reduced"]
    assert set(cfg["published"]) == set(conf["reduced"])
    assert (cfg["n_routed_experts"], cfg["held_experts"]) == (8, [0, 8])
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    kw = cfg["model"]["kwargs"]
    assert kw["n_layers"] == cfg["num_hidden_layers"]
    assert kw["vocab_size"] == cfg["vocab_size"]
    assert kw["moe"]["n_held"] == cfg["held_experts"][1]
    # The balance loss as published: the preset's coefficient, not an
    # override, and the reference reads the same from the file.
    from repro.configs import get_config

    moe = get_config(kw["preset"]).moe
    assert "router_aux_weight" not in kw["moe"] and moe.seq_aux
    assert cfg["seq_aux"] and moe.router_aux_weight == cfg["aux_loss_alpha"]
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("round_s", "round_mfu", "local_update_ms_per_round",
                 "assemble_ms_per_round", "device_idle_share",
                 "mla_ms_per_round", "moe_ms_per_round", "moe_experts_mfu"):
        assert CELL in metrics[name]["workloads"], name
    c = harness.load_cell(CELL, bench)
    assert c.spec_overrides["execution"]["clients_per_chunk"] == 1
    assert c.spec_overrides["fedavg"]["B"] == 4
    for key in compare.NUMBERS:
        lim = c.limits[key]
        assert lim["lower"] < lim["limit"] < lim["upper"], key
