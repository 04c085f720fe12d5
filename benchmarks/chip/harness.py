"""One run of one benchmark cell, driven by the files named in
``BENCHMARK.json``.

A cell (an entry of ``workloads``) joins a configuration and a traffic mix:

- ``configs/<config>.json``: the model by its registry name, its layer
  sizes and its client population; ``configs/<config>.py`` beside it is the
  plain reference of the model (``init``, ``apply``). The population is of
  images (``input_shape``, ``n_classes``) unless the file says
  ``"population": "tokens"``: token sequences for a next-token model, with
  ``vocab_size``, ``seq_len`` and ``n_topics`` (sequences of a topic share
  a token source; the partition splits on the topic). Both give
  ``clients`` and ``examples_per_client`` (images or sequences). With
  ``"reference_clients_per_block": c`` the reference trains the cohort c
  clients at a time, for a cohort whose copies of the weights do not fit
  on the chip at once.
- ``traffic/<traffic>.json``: overrides on the program's own
  ``ExperimentSpec`` JSON form (partition, C, E, B, lr, strategy, codec,
  execution lane) and how many rounds one timed call runs.
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from.
- ``flops/<model>.py``: the training FLOPs a round must do, from shapes.
- ``layer_metrics/<metric>.py``: one reader per per-layer metric. Its
  ``compute(ctx)`` gets the trace reduced two ways: ``ctx["trace"]``
  (``trace_reduce.Reduced``: device ops and idle time) and ``ctx["spans"]``
  (``span_reduce.Spans``: device time by the program's named scopes, and
  device idle time inside its host-loop spans).

Adding a configuration, a traffic mix, a cell or a metric adds such files
and ``BENCHMARK.json`` entries; nothing here changes.

The run: set-up (population and weights from the seed, the engine through
``RoundEngine.from_spec``, the cell's first three timed calls, which
compile or load every program the window runs), then the window of
``seconds`` (or, with ``trace``, a profiled stretch), then the reference
over the set-up's three calls and the verdict.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from benchmarks.chip import compare, population, span_reduce, trace_reduce
from benchmarks.chip.peaks import peak

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
SET_UP_STEPS = 3
TRACE_SECONDS = 3.0
ANNOTATION = "bench.call"


def log(msg: str) -> None:
    print(msg, flush=True)


# -- loading by name ----------------------------------------------------------

def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path
    )
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: Path, metric: str):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``."""
    return load_module(bench_dir / "layer_metrics" / f"{metric}.py")


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(path)
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    model: object          # configs/<config>.py
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path

    @property
    def spec_overrides(self) -> dict:
        return self.traffic["spec"]

    @property
    def rounds_per_call(self) -> int:
        return int(self.traffic["rounds_per_call"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_benchmark() if bench is None else bench
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    w = entries[name]
    config = _json(bench_dir / "configs" / f"{w['config']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        model=load_module(bench_dir / "configs" / f"{w['config']}.py"),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench_dir / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )


def validate(bench: dict, bench_dir: Path = BENCH_DIR) -> None:
    """Every cell loads, every metric has its reader or source, and every
    metric's ``workloads`` names cells that exist. Raises on the first
    fault."""
    cells = {w["name"] for w in bench["workloads"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        unknown = set(metric.get("workloads", ())) - cells
        if unknown:
            raise KeyError(f"metric {metric['name']!r} lists unknown cells "
                           f"{sorted(unknown)}")
    for metric in bench["per_layer"]:
        load_reader(bench_dir, metric["name"])
    for name in cells:
        cell = load_cell(name, bench, bench_dir)
        population.check_config(cell.config)
        block = cell.config.get("reference_clients_per_block")
        if block is not None and (not isinstance(block, int) or block < 1):
            raise ValueError(f"{name}: reference_clients_per_block must be a "
                             f"positive whole number, not {block!r}")
        named = [k for k in cell.limits if k in compare.NUMBERS]
        if not named or any("limit" not in cell.limits[k] for k in named):
            raise KeyError(f"limits/{name}.json names no number with a "
                           f"limit of {compare.NUMBERS}")


# -- the program under test ---------------------------------------------------

def derived_seed(seed: int) -> int:
    """A 31-bit seed for the program and the population, drawn from the
    run's seed (which may exceed 32 bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF


def build_spec(cell: Cell, seed: int):
    from repro.specs import ExperimentSpec

    s = cell.spec_overrides
    d = {
        "name": cell.name,
        "model": cell.config["model"],
        "partition": {**s["partition"], "n_clients": cell.config["clients"],
                      "seed": seed},
        "fedavg": {**s["fedavg"], "seed": seed},
        "strategy": s.get("strategy", {"kind": "fedavg"}),
        "codec": s.get("codec"),
        "execution": s.get("execution", {}),
    }
    return ExperimentSpec.from_json(json.dumps(d))


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, from
    JAX's own monitoring events, while registered."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0

    def _duration(self, event, duration, **kw):
        if event == self.BACKEND_COMPILE:
            self.count += 1

    def _event(self, event, **kw):
        if event == self.CACHE_HIT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


def host_tree(tree):
    import jax

    return jax.tree.map(lambda a: np.array(a, np.float32), tree)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class SetUp:
    """What set-up hands the window: the engine after its first calls, and
    what those calls produced for the comparison."""

    engine: object
    call: Callable[[], None]
    seed: int               # the program's and the population's seed
    clients: list
    p0: dict                # host copy of the weights the run started from
    prog: dict              # {"losses": [...], "params": {round: tree}}
    n_setup: int            # rounds run in set-up

    @property
    def snapshot_rounds(self):
        """Rounds after each set-up call."""
        return tuple(sorted(self.prog["params"]))


def set_up(cell: Cell, seed: int) -> SetUp:
    """Population and weights from ``seed``, the engine through the
    program's ``RoundEngine.from_spec``, then the cell's first
    ``SET_UP_STEPS`` timed calls: the first compiles (or loads) every
    program the window runs, and all of them are the comparison's input."""
    import jax

    from repro.core import RoundEngine

    marks = [("start", time.perf_counter())]
    s = derived_seed(seed)
    spec = build_spec(cell, s)
    clients = population.make_clients(
        cell.config, cell.spec_overrides["partition"], s
    )
    marks.append(("population", time.perf_counter()))
    init_params = jax.jit(lambda k: cell.model.init(
        jax.random.fold_in(jax.random.PRNGKey(k), 1), cell.config
    ))(s)
    p0 = host_tree(init_params)
    marks.append(("weights", time.perf_counter()))
    engine = RoundEngine.from_spec(spec, clients, init_params=init_params)
    del init_params
    marks.append(("engine", time.perf_counter()))
    R = cell.rounds_per_call
    exec_r = spec.execution.rounds_per_step

    def call():
        engine.run(R, rounds_per_step=exec_r)

    snaps = {}
    for i in range(SET_UP_STEPS):
        call()
        snaps[(i + 1) * R] = host_tree(engine.params)
        marks.append((f"call{i + 1}", time.perf_counter()))
    log("set-up phases: " + " ".join(
        f"{name}={t - prev:.3f}s"
        for (_, prev), (name, t) in zip(marks, marks[1:])
    ))
    n_setup = SET_UP_STEPS * R
    prog = {
        "losses": [r.train_loss for r in engine.history.records[:n_setup]],
        "params": snaps,
    }
    jax.block_until_ready(engine.params)
    return SetUp(engine, call, s, clients, p0, prog, n_setup)


def reference_of(cell: Cell, su: SetUp, **kw) -> dict:
    """The reference (or, through ``kw``, its control or a planted fault)
    over the rounds set-up ran."""
    from benchmarks.chip import reference

    return reference.run_reference(
        cell.model.apply, su.clients, su.p0, cell.spec_overrides, su.seed,
        su.n_setup, su.snapshot_rounds,
        clients_per_block=cell.config.get("reference_clients_per_block"),
        **kw,
    )


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Run the cell once and return the result line's object, ending with
    ``checks``. ``devices``: the chips the cell uses; ``t_start``: the
    process's start on ``time.perf_counter``'s clock."""
    import jax

    su = set_up(cell, seed)
    engine, R = su.engine, cell.rounds_per_call
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.4f} seed={seed} program_seed={su.seed} "
        f"rounds_per_call={R} compilations={engine.num_compilations}")

    tmp = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    calls = 0
    with CompileCounter() as compiles:
        if trace:
            jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        limit = min(seconds, TRACE_SECONDS) if trace else seconds
        while True:
            if trace:
                with jax.profiler.TraceAnnotation(ANNOTATION):
                    su.call()
            else:
                su.call()
            calls += 1
            if time.perf_counter() - t0 >= limit:
                break
        jax.block_until_ready(engine.params)
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    rounds = calls * R
    records = engine.history.records[su.n_setup:]
    walls = [r.wall_s for r in records]
    failed = sum(1 for r in records if not math.isfinite(r.train_loss))
    mem = memory_peak_bytes(devices)
    log(f"window_s={window_s:.4f} rounds={rounds} calls={calls} "
        f"window_compilations={compiles.count} "
        f"engine_compilations={engine.num_compilations} "
        f"memory_peak_bytes={mem} last_loss={records[-1].train_loss:.6f}")

    if trace:
        try:
            metrics, red = per_layer_metrics(cell, tmp, window_s, rounds,
                                             devices)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        metrics = end_to_end_metrics(cell, window_s, rounds, walls, setup_s)
    del engine
    su.engine = su.call = None
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_of(cell, su)
    log(f"reference_s={time.perf_counter() - t_ref:.2f}")
    log(f"losses={su.prog['losses']}")
    log(f"ref_losses={ref['losses']}")
    values = compare.readings(su.prog, ref, su.p0, su.snapshot_rounds,
                              cell.limits)
    limits = {k: cell.limits[k]["limit"] for k in values}
    values.update(window_compilations=compiles.count,
                  window_nonfinite_losses=failed)
    limits.update(window_compilations=0, window_nonfinite_losses=0)
    ok, checks = compare.judge(values, limits)
    dev = devices[0]
    out = {
        "correct": bool(ok),
        "attempted": rounds,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": mem,
        },
    }
    if trace:
        out["device"]["busy_s"] = red.mean_busy_s
        out["device"]["window_s"] = red.window_s
        out["breakdown"] = trace_reduce.breakdown(red)
    out["checks"] = checks
    return out


def end_to_end_metrics(cell, window_s, rounds, walls, setup_s) -> dict:
    values = {
        "round_s": window_s / rounds,
        "round_p90_s": float(np.percentile(walls, 90)) if walls else None,
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        v = values.get(m["name"])
        if v is None:
            raise KeyError(f"no end-to-end reading for {m['name']!r}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def per_layer_metrics(cell, trace_dir, window_s, rounds, devices):
    xplane = trace_reduce.find_xplane(trace_dir)
    red = trace_reduce.reduce_trace(xplane, ANNOTATION)
    spans = span_reduce.reduce_trace(xplane, ANNOTATION)
    fed = cell.spec_overrides["fedavg"]
    k = int(cell.config["clients"])
    m = max(int(round(float(fed["C"]) * k)), 1)
    n_k = int(cell.config["examples_per_client"])
    flops_file = cell.bench_dir / "flops" / f"{cell.config['model']['kind']}.py"
    ctx = {
        "trace": red,
        "spans": spans,
        "window_s": window_s,
        "rounds": rounds,
        "chips": len(devices),
        "peak": peak(devices[0].device_kind),
        "config": cell.config,
        "traffic": cell.traffic,
        "examples_per_round": m * int(fed["E"]) * n_k,
        "train_flops_per_example": (
            load_module(flops_file).train_flops_per_example(cell.config)
            if flops_file.is_file() else None
        ),
    }
    out = {}
    for metric in cell.per_layer:
        reader = load_reader(cell.bench_dir, metric["name"])
        v = reader.compute(ctx)
        if v is not None:
            out[metric["name"]] = {"value": float(v), "unit": metric["unit"]}
    log("trace: " + json.dumps({
        "window_s": red.window_s, "busy_s": red.busy_s,
        "top_ops": trace_reduce.breakdown(red, 25)["device_ops"],
        "spans_ms_per_round": span_reduce.per_round_ms(spans, rounds),
        "op_text": {k: v[:300] for k, v in sorted(
            red.op_text.items(), key=lambda kv: -red.op_s[kv[0]])[:25]},
    }))
    return out, red
