"""Finds a cell's pieces by name and turns one run into the result line.

``BENCHMARK.json`` names each cell's configuration (its ``file``), traffic
and chips.  By name the harness then finds, under ``benchmarks/chip/``:

- ``traffic/<traffic>.json`` — the mix, whose ``job`` names
- ``jobs/<job>.py`` — one set-up, window and check, ``run(run)`` returning a
  :class:`~.training.Result`;
- ``limits/<cell>.json`` — the limit of every number the cell's check compares;
- ``metrics/<metric>.py`` — one reader per per-layer metric, ``read(ctx)``
  returning a number or ``None`` where it finds nothing to read;
- ``chipbench/peaks.json`` — the chip's peaks, keyed by ``device_kind``.

A new configuration, mix, job or metric is new files and new entries, no edit.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent          # benchmarks/chip/chipbench
BENCH = HERE.parent                             # benchmarks/chip
ROOT = BENCH.parents[1]                         # the checkout


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def cell_of(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(spec: Dict[str, Any], name: str, root: Path = ROOT) -> Dict[str, Any]:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_of(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return load_json(bench / "traffic" / f"{name}.json")


def limits_of(cell: str, bench: Path = BENCH) -> Dict[str, float]:
    return load_json(bench / "limits" / f"{cell}.json")


def _load(kind: str, name: str, bench: Path):
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    return _load("metrics", name, bench).read


def job_runner(name: str, bench: Path = BENCH):
    return _load("jobs", name, bench).run


def metrics_for(spec: Dict[str, Any], cell: str, trace: bool) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in spec["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]


def peaks_of(kind: str) -> Dict[str, Any]:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def require_chip(chips: int):
    """The devices of the run; raises :class:`NoChip` where there is no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """JAX's persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` points), every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileClock:
    """Seconds and events of JAX's backend compiles (persistent-cache hits included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.events += 1


class Window:
    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds = 0.0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Run:
    """What a job sees: the cell's files, the seed, and the clocks."""

    def __init__(self, *, cell, config, traffic, limits, seed, seconds, trace, t_start, clock):
        self.cell, self.config, self.traffic, self.limits = cell, config, traffic, limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start, self.clock = t_start, clock
        self.setup_s: Optional[float] = None
        self.setup_compile_s = 0.0
        self.window_compiles = 0
        self.trace_summary: Optional[Dict[str, Any]] = None
        self.window_start = 0.0
        self.window_s = 0.0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.setup_compile_s = self.clock.seconds

    @contextlib.contextmanager
    def window(self):
        import jax

        from . import spans, trace as trace_mod

        log_dir = None
        if self.trace:
            log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        events0 = self.clock.events
        w = Window()
        self.window_start = w.t0
        try:
            with spans.span(trace_mod.WINDOW):
                yield w
        finally:
            w.seconds = self.window_s = w.elapsed()
            self.window_compiles = self.clock.events - events0
            if log_dir is not None:
                jax.profiler.stop_trace()
        if log_dir is not None:
            try:
                self.trace_summary = trace_mod.reduce(trace_mod.extract(log_dir))
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)


class Context:
    """What a per-layer metric's reader sees."""

    def __init__(self, run: Run, result, peaks: Dict[str, Any], chips: int):
        from . import spans

        self.run, self.result, self.peaks, self.chips = run, result, peaks, chips
        self.trace = run.trace_summary
        self.setup_compile_s = run.setup_compile_s
        self.steps, self.saves = result.steps, result.saves
        self.step_flops = result.step_flops
        self._spans = spans.recorded(run.window_start)

    def span_seconds(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self._spans if n == name]

    @staticmethod
    def mean(values) -> Optional[float]:
        values = list(values)
        return statistics.fmean(values) if values else None


def run_cell(args, *, t_start: float, spec=None, root: Path = ROOT, bench: Path = BENCH,
             chip_check=require_chip, compile_cache: bool = True) -> Dict[str, Any]:
    """Set up, measure and check one cell; returns the result line.

    ``t_start`` is the process's first ``perf_counter`` reading: set-up
    counts from there, JAX's start included."""
    spec = spec if spec is not None else load_spec(root)
    cell = cell_of(spec, args.workload)
    config = config_of(spec, cell["config"], root)
    traffic = traffic_of(cell["traffic"], bench)
    limits = limits_of(cell["name"], bench)
    job = job_runner(traffic["job"], bench)
    wanted = metrics_for(spec, cell["name"], bool(args.trace))
    readers = {m["name"]: metric_reader(m["name"], bench) for m in wanted} if args.trace else {}

    devices = chip_check(cell["chips"])
    import jax

    peaks = peaks_of(devices[0].device_kind) if devices[0].platform == "tpu" else None
    if compile_cache:
        enable_compile_cache()
    clock = CompileClock()
    run = Run(cell=cell, config=config, traffic=traffic, limits=limits, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), t_start=t_start, clock=clock)
    result = job(run)
    result.e2e["setup_s"] = run.setup_s

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        ctx = Context(run, result, peaks, cell["chips"])
        for m in wanted:
            value = readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in wanted:
            metrics[m["name"]] = {"value": float(result.e2e[m["name"]]), "unit": m["unit"]}

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in result.checks) and result.failed == 0,
        "attempted": result.attempted,
        "failed": result.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        line["breakdown"] = {k: run.trace_summary[k] for k in ("device_ops", "idle_gaps")}
    line["info"] = {"window_s": run.window_s, "compiles_in_window": run.window_compiles,
                    "setup_compile_s": run.setup_compile_s}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in result.checks}
    for c in result.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'} ({c.note})",
              file=sys.stderr)
    return line
