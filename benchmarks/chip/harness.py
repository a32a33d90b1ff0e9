"""Finds a cell's files by the names in ``BENCHMARK.json`` and runs it.

Everything that belongs to one cell sits in files of its own, found by
name, so that a cell or a metric is added with new files and a new
``BENCHMARK.json`` entry and never by editing a file:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* the configuration is the JSON file that ``configs[].file`` gives;
* the traffic mix is ``traffic/<traffic>.json``, which names its driver
  and holds its parameters and the limits of its output check;
* the driver is ``drivers/<driver>.py``, whose ``run(spec)`` drives the
  program's own entry point and returns a :class:`Run`;
* each per-layer metric is ``metrics/<name>.py``, whose ``read(ctx)``
  returns the metric, or None where it finds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Scratch space inside the checkout (git-ignored) at fixed paths: traces
# and checkpoints of the current run, wiped before each use.
SCRATCH = ROOT / ".bench_scratch"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    pass


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = HERE) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = _named(bench["workloads"], name, "workload")
    conf = _named(bench["configs"], entry["config"], "configuration")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(
        name=name, chips=entry["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{entry['traffic']}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"BENCHMARK.json has no {what} named {name!r}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    if spec is None or not path.exists():
        raise BenchError(f"missing benchmark file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_refusal(devices, chips: int) -> Optional[str]:
    """Why this process may not measure the cell; None where it may."""
    from peaks import UnknownDevice, peaks_for
    if devices[0].platform != "tpu":
        return (f"the benchmark measures TPUs only; JAX found "
                f"{len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        return f"the cell needs {chips} chips; JAX found {len(devices)}"
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        return str(e)
    return None


def arch_config(config: dict, rehearsal: bool):
    """The program's ArchConfig for a configuration file; in a rehearsal,
    the program's own reduced sizes of it."""
    from repro.configs.base import ArchConfig
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(**{k: v for k, v in config.items() if k in names})
    return arch.reduced() if rehearsal else arch


def reference_config(config: dict, arch) -> dict:
    """The configuration as the reference reads it, at the sizes run."""
    out = dict(config)
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "sliding_window"):
        out[k] = getattr(arch, k)
    out["head_dim"] = arch.hd
    return out


# ---------------------------------------------------------- the window
class Window:
    """The measured window of one run: its host-clock ends, the programs
    compiled (or fetched from the compile cache) inside it, and, in a
    traced run, the profiler trace of it.

    ``start`` and ``stop`` may be called from inside the program's loop
    (the training driver calls them from the registry it passes in).
    """

    def __init__(self, trace_dir: Optional[Path]):
        self.trace_dir = trace_dir
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.compiles = 0
        self._counting = False

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if self._counting and event == BACKEND_COMPILE_EVENT:
            self.compiles += 1

    def start(self) -> None:
        import jax
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            # the first program after the profiler starts stalls for
            # seconds on a TPU (seen on a v5e); let that be set-up
            jax.block_until_ready(jax.numpy.zeros(()) + 1)
        self._counting = True
        self.t0 = time.perf_counter()
        self._span = jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        self._counting = False
        if self.trace_dir is not None:
            jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @contextmanager
    def listening(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        try:
            yield self
        finally:
            if self.t0 is not None and self.t1 is None:
                self.stop()
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)


@dataclass(frozen=True)
class Spec:
    """What a driver is given for one run."""
    cell: Cell
    arch: Any                  # the program's ArchConfig, at the run's sizes
    ref_cfg: dict              # the configuration as the reference reads it
    traffic: dict              # the traffic parameters, at the run's sizes
    seed: int
    seconds: float
    t_start: float             # host clock at process start
    window: Window
    scratch: Path


@dataclass
class Run:
    """What a driver returns."""
    end_to_end: dict           # metric name -> value, host clock
    attempted: int
    failed: int
    checks: dict               # name -> (value, limit)
    memory_peak_bytes: int
    work: dict = field(default_factory=dict)   # for the per-layer readers


@dataclass(frozen=True)
class Context:
    """What a per-layer metric's reader is given."""
    config: dict
    traffic: dict
    run: Run
    window_s: float
    compiles: int
    peaks: Any
    trace: Any                 # devtrace.Trace of the window, or None


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value that at least ``q``
    percent of the values do not exceed."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def gap_vs(prog: dict, ref: dict, leaves: Optional[list] = None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    leaves = list(ref) if leaves is None else leaves
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def train_gaps(prog: dict, ref: dict) -> dict:
    """The training check's numbers: the worst step's relative loss gap,
    and the worst leaf's gap of the first gradient's norm and of the
    parameters' change. Both arguments hold ``losses``,
    ``first_grad_norms`` and ``change_norms`` as
    ``reference.train_reference`` returns them. Leaves whose reference
    gradient is nought to rounding (under a thousandth of the median
    leaf's) move under Adam by round-off alone, so their change is not
    compared."""
    grads = ref["first_grad_norms"]
    med = statistics.median(grads.values())
    moving = [k for k, g in grads.items() if g >= 1e-3 * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "first_grad_gap": gap_vs(prog["first_grad_norms"], grads),
        "change_gap": gap_vs(prog["change_norms"], ref["change_norms"],
                             moving)}


def is_correct(checks: dict, failed: int = 0) -> bool:
    """A run is correct where it compared something, no request or step
    failed, and every number compared lies within its limit. ``checks``
    maps a name to (value, limit)."""
    return (bool(checks) and failed == 0
            and all(v <= lim for v, lim in checks.values()))


# ----------------------------------------------------------- a whole run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: bool = False) -> dict:
    """Run one cell and return the result line as a dict. A rehearsal
    runs at the program's reduced sizes, on any device, and its result
    must never be printed as a measurement."""
    import jax
    from peaks import peaks_for
    driver = load_module(cell.bench_dir / "drivers"
                         / f"{cell.traffic['driver']}.py")
    arch = arch_config(cell.config, rehearsal)
    traffic = dict(cell.traffic, **(cell.traffic.get("rehearsal", {})
                                   if rehearsal else {}))
    run_dir = SCRATCH / cell.name
    window = Window(run_dir / "trace" if trace else None)
    spec = Spec(cell=cell, arch=arch,
                ref_cfg=reference_config(cell.config, arch), traffic=traffic,
                seed=seed, seconds=seconds, t_start=t_start, window=window,
                scratch=run_dir)
    with window.listening():
        run = driver.run(spec)
    if window.t1 is None:
        raise BenchError(f"driver {cell.traffic['driver']} measured no window")
    print(f"window: {window.seconds!r} s by the host clock; "
          f"{window.compiles} programs compiled or fetched from the "
          f"compile cache inside it", file=sys.stderr)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result: dict = {"attempted": run.attempted, "failed": run.failed}
    if trace:
        import devtrace
        tr = devtrace.Trace.from_dir(window.trace_dir)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        ctx = Context(config=spec.ref_cfg, traffic=traffic, run=run,
                      window_s=window.seconds, compiles=window.compiles,
                      peaks=None if rehearsal else peaks_for(
                          devices[0].device_kind),
                      trace=tr)
        metrics = {}
        for m in cell.per_layer:
            value = load_module(cell.bench_dir / "metrics"
                                / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
    else:
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in run.end_to_end]
        if missing:
            raise BenchError(f"driver reported no {missing}")
        metrics = {m["name"]: {"value": run.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": is_correct(run.checks, run.failed),
              **result, "metrics": metrics, "device": device}
    # the numbers compared come last, each beside its limit
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    return result
