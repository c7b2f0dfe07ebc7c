"""The benchmark's shared harness.

It finds every piece of a cell by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the model or kernel and its sizes.
  Its ``kind`` names the driver, ``bench/kinds/<kind>.py``; a serving
  configuration's ``reference`` names its plain reference,
  ``bench/reference/<reference>.py``;
* ``bench/traffic/<traffic>.json``: the traffic's parameters; its
  ``generator`` names ``bench/generators/<generator>.py``;
* ``bench/metrics/<metric>.py`` (or ``<family>.py`` for a name
  ``<family>.<suffix>``): one reader per per-layer metric.

A kind's ``run(cell)`` sets up, warms up, drives the window and checks
its outputs; it returns an ``Outcome``. With ``cell.control`` the kind
puts its control (the precision below the configuration's) in the
program's place and holds it to the same limits: such a run comes out
not correct. ``bench/calibrate.py`` sets it; a benchmark run never does. This module guards the device,
reduces the trace, calls the readers, and prints the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def load_json(sub: str, name: str) -> Dict[str, Any]:
    return json.loads((BENCH / sub / f"{name}.json").read_text())


def reader_path(metric: str) -> pathlib.Path:
    """``metrics/<name>.py``, else ``metrics/<family>.py`` for a name
    ``<family>.<suffix>`` (one reader serves ``serve_mfu.chat`` and any
    later ``serve_mfu.<mix>``)."""
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.exists() else BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def load_reader(metric: str) -> Callable[["Run"], Optional[float]]:
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: Dict[str, Any], workload: str, section: str,
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[section]
            if workload in m.get("workloads", [workload])]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# What a kind gets and returns
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """One run of one cell, as the command line and the files give it."""

    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    chips: int
    t0: float                              # perf_counter at process start
    trace_dir: Optional[str] = None        # set while a trace is open
    compiles: Optional[CompileCounter] = None
    control: bool = False                  # the control in the program's place

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed part of the window (``--trace 1`` only).
        The span ``bench.window`` marks it on the trace's own clock."""
        if not self.trace:
            yield
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the benchmark's own spans and the device, not every Python call
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    @property
    def limits(self) -> Dict[str, float]:
        """The limits of the check: the configuration's, and the traffic
        file's where a mix has its own (a dot cell's scheme)."""
        return {**self.config.get("limits", {}), **self.traffic.get("limits", {})}

    def span(self, name: str):
        """A host span on the profiler's clock; free when not tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]                  # end-to-end values (host clock)
    checks: Dict[str, Tuple[float, float]]  # name -> (value, limit)
    attempted: int
    failed: int
    records: Dict[str, Any]                # what the per-layer readers read
    memory_peak_bytes: int
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(v <= lim for v, lim in
                                        self.checks.values())


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""

    cell: Cell
    outcome: Outcome
    trace: Any                              # trace_reduce.Trace
    peak: Dict[str, float]                  # peaks.json row of this device

    @property
    def records(self) -> Dict[str, Any]:
        return self.outcome.records


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def devices(chips: int):
    """The cell's chips, or ``NoChip``: nothing runs on another backend."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peak_row(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Programs JAX compiled or loaded from its cache (``programs``), and
    those it had to compile (``misses``), since ``reset``."""

    def __init__(self):
        import jax

        self.programs = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def reset(self) -> Tuple[int, int]:
        out = (self.programs, self.misses)
        self.programs = self.misses = 0
        return out


def enable_cache() -> str:
    """The program's persistent compile cache, at the fixed directory the
    benchmark gives it (``<checkout>/.jax_cache``), with every program
    cached however fast it compiled, so a warm run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def make_cell(workload: str, seed: int, seconds: float, trace: bool,
              t0: float, spec: Optional[Dict[str, Any]] = None) -> Cell:
    spec = spec or load_spec()
    w = find(spec["workloads"], workload, "workload")
    return Cell(workload=workload, config=load_json("configs", w["config"]),
                traffic=load_json("traffic", w["traffic"]), seed=seed,
                seconds=seconds, trace=trace, chips=w["chips"], t0=t0)


def result(cell: Cell, outcome: Outcome, spec: Dict[str, Any],
           device: Dict[str, Any]) -> Dict[str, Any]:
    """The result line: end-to-end metrics, or with ``--trace 1`` the
    per-layer ones; the numbers compared come last."""
    line: Dict[str, Any] = {"correct": outcome.correct,
                            "attempted": outcome.attempted,
                            "failed": outcome.failed}
    metrics: Dict[str, Any] = {}
    if not cell.trace:
        for m in cell_metrics(spec, cell.workload, "end_to_end"):
            metrics[m["name"]] = {"value": outcome.e2e[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import trace_reduce

        t = time.perf_counter()
        tr = trace_reduce.load(cell.trace_dir)
        run = Run(cell, outcome, tr, peak_row(device["kind"]))
        for m in cell_metrics(spec, cell.workload, "per_layer"):
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=tr.busy_s, window_s=tr.window_s)
        line["breakdown"] = tr.breakdown()
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        outcome.notes["trace_reduce_s"] = time.perf_counter() - t
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in outcome.checks.items()}
    return line


def run_cell(cell: Cell, spec: Dict[str, Any], *, require_chip: bool = True,
             ) -> Dict[str, Any]:
    """Set up, measure and check one cell; returns the result line.
    ``require_chip=False`` is for the tests, which drive a run on the
    CPU at a small size."""
    return measure(cell, spec, require_chip=require_chip)[0]


def measure(cell: Cell, spec: Dict[str, Any], *, require_chip: bool = True,
            ) -> Tuple[Dict[str, Any], Outcome]:
    """``run_cell``, and the kind's ``Outcome`` with its notes."""
    import jax

    devs = devices(cell.chips) if require_chip else jax.devices()[:cell.chips]
    if require_chip:
        log(f"compile_cache={enable_cache()}")
    kind = importlib.import_module(f"bench.kinds.{cell.config['kind']}")
    cell.compiles = cell.compiles or CompileCounter()
    outcome = kind.run(cell)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": outcome.memory_peak_bytes}
    line = result(cell, outcome, spec, device)
    for k, v in outcome.notes.items():
        log(f"note {k}={v}")
    for k, (v, lim) in outcome.checks.items():
        log(f"check {k}={v!r} limit={lim!r} {'ok' if v <= lim else 'FAIL'}")
    return line, outcome


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter() if t0 is None else t0
    spec = load_spec()
    cell = make_cell(args.workload, args.seed, args.seconds,
                     bool(args.trace), t0, spec)
    try:
        line = run_cell(cell, spec)
    except NoChip as e:
        log(f"bench: {e}; nothing runs on another backend")
        return 2
    print(json.dumps(line), flush=True)
    return 0
