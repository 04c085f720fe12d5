"""fedlint: the seeded-violation corpus, suppression semantics, the
legacy-seed quarantine, the CLI contract, and the repo-tree invariant
(`src/` and `tests/` lint clean) that the CI lint lane enforces.

The bad fixtures carry `# expect: FN` markers; the tests assert the
findings match the markers EXACTLY — 100% of seeded violations found, at
the marked lines, with zero extras — and that every clean twin is empty
(zero false positives).
"""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import lint_source, run_paths
from repro.analysis.core import RULES, is_legacy_seed

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "fedlint"
_EXPECT = re.compile(r"#\s*expect:\s*(F\d)")
ALL_RULES = ("F1", "F2", "F3", "F4", "F5", "F6")


def _expected(path: Path):
    return sorted(
        (m.group(1), i)
        for i, line in enumerate(path.read_text().splitlines(), 1)
        for m in [_EXPECT.search(line)]
        if m
    )


def test_registry_covers_all_families():
    assert set(RULES) == set(ALL_RULES)


@pytest.mark.parametrize("family", [r.lower() for r in ALL_RULES])
def test_bad_fixture_exact_hits(family):
    path = FIXTURES / f"{family}_bad.py"
    got = sorted(
        (f.rule, f.line) for f in lint_source(path.read_text(), str(path))
    )
    exp = _expected(path)
    assert len(exp) >= 2, "corpus contract: >= 2 seeded violations per rule"
    assert got == exp


@pytest.mark.parametrize("family", [r.lower() for r in ALL_RULES])
def test_clean_twin_has_zero_findings(family):
    path = FIXTURES / f"{family}_clean.py"
    assert lint_source(path.read_text(), str(path)) == []


def test_f5_gossip_bad_fixture_exact_hits():
    """The gossip-mix-shaped F5 corpus: one-hot + mixing matmuls without
    an accumulation dtype, and a node grid with plain floor division."""
    path = FIXTURES / "f5_gossip_bad.py"
    got = sorted(
        (f.rule, f.line) for f in lint_source(path.read_text(), str(path))
    )
    exp = _expected(path)
    assert len(exp) >= 2, "corpus contract: >= 2 seeded violations"
    assert got == exp
    assert {r for r, _ in got} == {"F5"}


def test_f5_gossip_clean_twin_has_zero_findings():
    path = FIXTURES / "f5_gossip_clean.py"
    assert lint_source(path.read_text(), str(path)) == []


def test_gossip_mix_kernel_is_lint_clean():
    """The shipped neighbor-mixing kernel honors the F5 contracts it is
    the newest subject of (pinned here so a refactor that drops
    preferred_element_type or the pad idiom fails fast)."""
    report = run_paths(
        [str(REPO / "src" / "repro" / "kernels" / "gossip_mix.py")]
    )
    assert report.parse_errors == [] and report.findings == []


@pytest.mark.parametrize("wrap", ["jax.shard_map", "shard_map"])
def test_shard_map_body_is_traced(wrap):
    """A function handed to ``jax.shard_map`` (the engine's cohort-sharded
    round) is a traced body, as under the older bare ``shard_map``."""
    import ast

    from repro.analysis.trace import TraceIndex

    src = (
        "def body(x):\n    return x\n\n"
        f"f = {wrap}(body, mesh=m, in_specs=s, out_specs=s, check_vma=False)\n"
    )
    traced = TraceIndex(ast.parse(src)).traced
    assert [fn.node.name for fn in traced] == ["body"]
    assert traced[0].reason == wrap


def test_suppression_comments_silence_findings():
    path = FIXTURES / "suppressed.py"
    src = path.read_text()
    assert lint_source(src, str(path)) == []
    # ... and they are load-bearing: stripping the directives resurfaces
    # the violations (guards against the rules simply not firing).
    stripped = re.sub(r"#\s*fedlint:[^\n]*", "", src)
    resurfaced = lint_source(stripped, str(path))
    assert {f.rule for f in resurfaced} == {"F2", "F3"}


def test_file_level_disable():
    src = (
        "# fedlint: disable-file=F2\n"
        "import jax\n\n\n"
        "def f(key, n):\n"
        "    x = jax.random.normal(key, (n,))\n"
        "    return x + jax.random.uniform(key, (n,))\n"
    )
    assert lint_source(src) == []
    assert len(lint_source(src.replace("# fedlint: disable-file=F2\n", ""))) == 1


def test_legacy_seed_files_are_skipped_but_reported():
    path = FIXTURES / "legacy_seed.py"
    assert is_legacy_seed(path.read_text())
    report = run_paths([str(path)])
    assert report.findings == []
    assert report.files_scanned == 0
    assert [Path(p).name for p in report.skipped_legacy] == ["legacy_seed.py"]


def test_fixtures_dir_excluded_from_tree_walks():
    report = run_paths([str(FIXTURES.parent.parent)])  # tests/
    assert not any("fixtures" in f.path for f in report.findings)


def test_benchmark_seed_scaffolding_is_quarantined():
    # ROADMAP marks these as unported to the RoundEngine; the lint surface
    # must show them as quarantined, not silently clean.
    report = run_paths([str(REPO / "benchmarks")])
    names = {Path(p).name for p in report.skipped_legacy}
    assert {"table3_cifar.py", "shakespeare_lstm.py"} <= names


def test_src_and_tests_lint_clean():
    report = run_paths([str(REPO / "src"), str(REPO / "tests")])
    assert report.parse_errors == []
    assert report.findings == [], "\n" + "\n".join(
        f.format() for f in report.findings
    )


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=REPO,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
    )


def test_cli_exit_codes_and_json():
    bad = str(FIXTURES / "f2_bad.py")
    # findings alone don't fail the run...
    r = _cli(bad)
    assert r.returncode == 0, r.stderr
    assert "F2" in r.stdout
    # ...--fail-on-findings does (the CI lane contract), and --json is
    # machine-readable with exact positions.
    r = _cli(bad, "--json", "--fail-on-findings")
    assert r.returncode == 2, r.stderr
    payload = json.loads(r.stdout)
    assert [(f["rule"], f["line"]) for f in payload["findings"]] == [
        ("F2", 7), ("F2", 15)
    ]
    # a clean file exits 0 even under --fail-on-findings
    r = _cli(str(FIXTURES / "f2_clean.py"), "--fail-on-findings")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_rule_subset_and_listing():
    r = _cli("--list-rules")
    assert r.returncode == 0
    for rule in ALL_RULES:
        assert rule in r.stdout
    r = _cli(str(FIXTURES / "f3_bad.py"), "--rules", "F1")
    assert r.returncode == 0
    assert "F3" not in r.stdout.replace("0 finding", "")
