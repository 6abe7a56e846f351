"""What every cell shares: finding its files by name, the device check,
the compile cache, the seed, the measured window and the result line.

A cell is an entry of `workloads` in BENCHMARK.json. Its configuration is
`configs/<config>.json`, its traffic `traffic/<traffic>.json` (which names
the driver, `drivers/<driver>.py`), its correctness limits
`limits/<workload>.json`, and each per-layer metric `metrics/<name>.py`,
or, for a quantity split by kind (`device_idle.decode`), the reader of the
quantity, `metrics/device_idle.py`. Adding a cell adds such files and
entries; no file here names a cell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import tracing
from chipbench.peaks import peaks_for
from chipbench.weights import key_data

BENCH = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = BENCH.parents[1]                            # the checkout

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class BenchError(SystemExit):
    """Ends the run with a message and no result line."""

    def __init__(self, msg: str):
        super().__init__(f"chipbench: {msg}")


def read_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path} not found") from None


def load_module(path: Path, name: str):
    """A driver or metric reader, found by its file name."""
    if not path.is_file():
        raise BenchError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Path:
    """The reader of a per-layer metric: its own file, else the file of the
    quantity it splits by kind."""
    own = BENCH / "metrics" / f"{name}.py"
    return own if own.is_file() else BENCH / "metrics" / \
        f"{name.split('.')[0]}.py"


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    spec: Dict[str, Any]

    @classmethod
    def find(cls, spec: Dict[str, Any], name: str) -> "Cell":
        w = next((w for w in spec["workloads"] if w["name"] == name), None)
        if w is None:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        c = next((c for c in spec["configs"] if c["name"] == w["config"]),
                 None)
        if c is None:
            raise BenchError(f"no configuration {w['config']!r}")
        return cls(name, int(w["chips"]), c["name"], read_json(ROOT / c["file"]),
                   w["traffic"],
                   read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                   read_json(BENCH / "limits" / f"{name}.json"), spec)

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> List[Dict[str, Any]]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


# program fields a configuration file may set; the widths must already
# equal the program's, and only the depth may be cut
_DEPTH = "n_layers"


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: its `arch`,
    with the depth the file gives, checked field by field against
    `as_run`. A program whose widths differ from the file is an error."""
    from repro.configs import get_arch
    cfg = get_arch(config["arch"])
    as_run = config["as_run"]
    if _DEPTH in as_run:
        cfg = dataclasses.replace(cfg, n_layers=int(as_run[_DEPTH]))
    for key, want in as_run.items():
        got = cfg
        for part in key.split("."):
            got = getattr(got, part)
        if got != want:
            raise BenchError(f"{config['arch']}: the program has {key}={got!r}"
                             f", the configuration file {want!r}")
    return cfg


class CompileWatch:
    """Counts backend compiles and persistent-cache hits while armed."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def install(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.armed and event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **kw):
        if self.armed and event == CACHE_HIT:
            self.cache_hits += 1


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    layer: Dict[str, Any] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)
    kept: Dict[str, Any] = field(default_factory=dict)   # for control.py


class Bench:
    """One run of one cell."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t0: float, devices=None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = t0
        self.devices = devices
        self.key = key_data(seed)
        self.rng = np.random.default_rng(self.seed)
        self.watch = CompileWatch()
        self.t_start: Optional[float] = None
        self.t_end: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.trace_dir: Optional[str] = None
        self.trace_data: Optional[tracing.Trace] = None

    # -- devices -------------------------------------------------------------
    @classmethod
    def on_chip(cls, cell: Cell, seed: int, seconds: float, trace: bool,
                t0: float) -> "Bench":
        """The cell's chips, or no run: there is no CPU fallback."""
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX sees {len(devs)} "
                             f"{devs[0].platform} device(s)")
        if len(devs) < cell.chips:
            raise BenchError(f"{cell.name} needs {cell.chips} chips, "
                             f"{len(devs)} found")
        try:
            peaks_for(devs[0].device_kind)
        except KeyError as e:
            raise BenchError(str(e)) from None
        b = cls(cell, seed, seconds, trace, t0, devs[:cell.chips])
        b.use_compile_cache()
        b.watch.install()
        return b

    @property
    def peaks(self):
        return peaks_for(self.devices[0].device_kind)

    @staticmethod
    def use_compile_cache() -> str:
        """JAX's persistent cache at a fixed path in the checkout (or where
        JAX_COMPILATION_CACHE_DIR says), keeping every program, so that
        only a cell's first run in a checkout compiles."""
        import jax
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        return path

    def read_memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak = max(peaks)
        return self.memory_peak

    # -- the measured window -------------------------------------------------
    @contextlib.contextmanager
    def window(self):
        """Set-up ends here. Traces the window when asked, and counts what
        compiles inside it."""
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        with tracing.capture(self.trace_dir, self.trace):
            self.watch.armed = True
            self.t_start = time.monotonic()
            try:
                with tracing.annotate(tracing.WINDOW_SPAN):
                    yield self
            finally:
                if self.t_end is None:
                    self.t_end = time.monotonic()
                self.watch.armed = False

    def end_window(self, t: float) -> None:
        """The window ends at `t`: the end of the last unit of work that
        started inside it."""
        self.t_end = t

    @property
    def setup_s(self) -> float:
        return self.t_start - self.t0

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def load_trace(self) -> Optional[tracing.Trace]:
        if self.trace_dir and self.trace_data is None:
            self.trace_data = tracing.load(self.trace_dir)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return self.trace_data


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
