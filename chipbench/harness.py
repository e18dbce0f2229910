"""What every cell shares: finding files by name, the chip, the compile
cache, compile counting, host spans, statistics and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files under ``configs/``, ``traffic/``, ``workloads/``, ``drivers/`` and
``metrics/``, loaded by the names the cell file gives.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: fixed in-checkout path: the path is part of the cache's key
DEFAULT_CACHE_DIR = ROOT / ".jax_cache"


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str) -> None:
        super().__init__(f"chipbench: {msg}")


# -- files by name ---------------------------------------------------------
def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """Import ``chipbench/<kind>/<name>.py`` by its file name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module named {name!r} ({path})")
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One cell with its configuration and traffic, as the files give them."""
    name: str
    spec: dict
    config: dict
    traffic: dict

    @classmethod
    def load(cls, name: str) -> "Cell":
        spec = load_json("workloads", name)
        return cls(name=name, spec=spec,
                   config=load_json("configs", spec["config"]),
                   traffic=load_json("traffic", spec["traffic"]))

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def cell_spec(run: "Run") -> tuple[dict, dict, dict]:
    """The cell's parameters, traffic and configuration; a rehearsal takes
    the cell's ``rehearse`` block over them (its ``traffic`` and
    ``config`` keys over the traffic and the configuration)."""
    cell = dict(run.cell.spec)
    tr = dict(run.cell.traffic)
    cfg = dict(run.cell.config)
    if run.notes.get("rehearse"):
        r = cell.get("rehearse", {})
        cell.update({k: v for k, v in r.items() if k not in ("traffic", "config")})
        tr.update(r.get("traffic", {}))
        cfg.update(cfg.get("rehearse", {}))
        cfg.update(r.get("config", {}))
    return cell, tr, cfg


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; have {sorted(table['devices'])}") from None


# -- the chip --------------------------------------------------------------
def require_devices(chips: int, *, rehearse: bool):
    """The devices a cell runs on; without them, exit non-zero and print
    no result.  Under ``rehearse`` the CPU stands in (tests only)."""
    import jax

    devs = jax.devices()
    if rehearse:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found {devs[0].platform!r}, not a TPU; the "
                         f"benchmark measures on the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def use_cache(cache_dir: Optional[str]) -> Path:
    """JAX's persistent compilation cache at one fixed directory, handed to
    the program's own cache setup; every executable is kept, however
    quickly it compiled, so a checkout's second run compiles nothing."""
    import jax

    path = Path(cache_dir) if cache_dir else DEFAULT_CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    from repro.launch.compile_cache import use_compile_cache

    got = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return got


class CompileCounter:
    """Counts executables built (compiled, or loaded from the persistent
    cache) and backend compile seconds, from ``jax.monitoring``."""

    def __init__(self) -> None:
        self.requests = 0       # executables built through the cache path
        self.misses = 0         # ... that had to compile
        self.compile_s = 0.0

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.misses


def span(name: str):
    """A host span in the profiler's trace (a no-op when none is taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- a run -----------------------------------------------------------------
@dataclass
class Check:
    """One number compared for ``correct``, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    """What a driver hands back, and what the metric readers read."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    device: Any = None
    setup_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: end-to-end metric name -> value (host clock over the window)
    end_to_end: dict = field(default_factory=dict)
    #: program counters over the window, by the names the readers use
    counters: dict = field(default_factory=dict)
    #: work the window's calls needed, per kernel or step, from work/
    work: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)
    memory_peak_bytes: int = 0
    window_s: float = 0.0
    #: the reduced trace (trace_reduce.Reduced) of a traced run
    reduced: Any = None
    notes: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


class Window:
    """The measured window: optional profiler trace around it.

    Entering starts the trace (when asked for) and the clock; ``close``
    stops both after the caller has waited for the device, so the trace
    holds every operation the window dispatched.
    """

    def __init__(self, seconds: float, trace_dir: Optional[Path]) -> None:
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t0 = 0.0
        self.t1 = 0.0
        self.tracing = False

    def open(self) -> float:
        import jax

        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # the benchmark's spans only
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self.tracing = True
            self._span = jax.profiler.TraceAnnotation("cb.window")
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def close(self) -> float:
        import jax

        self.t1 = time.perf_counter()
        if self.tracing:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False
        return self.t1


def emit(run: Run, metric_names: list[str], readers: dict[str, Callable]) -> dict:
    """The result line: the cell's metrics of this kind, the device, and the
    numbers compared beside their limits (the key that comes last)."""
    metrics = {}
    for name in metric_names:
        if name in run.end_to_end and not run.trace:
            val, unit = run.end_to_end[name]
        elif name in readers:
            got = readers[name].read(run)
            if got is None:
                continue        # nothing to read in this run: left out
            val, unit = got, readers[name].UNIT
        else:
            continue
        metrics[name] = {"value": float(val), "unit": unit}
    dev = run.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": run.cell.spec["chips"],
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    line: dict = {"correct": run.correct, "attempted": int(run.attempted),
                  "failed": int(run.failed), "metrics": metrics,
                  "device": device}
    if run.trace and run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        line["breakdown"] = run.reduced.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    return line


def print_checks(run: Run) -> None:
    """The numbers compared, each beside its limit, as stderr's last lines."""
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"correct: {run.correct}", file=sys.stderr, flush=True)

