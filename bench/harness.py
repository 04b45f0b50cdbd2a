"""What every cell of the benchmark shares: finding a cell's files by name,
the device check, compile accounting and the table of peaks.

Nothing here names a configuration, a traffic mix or a metric: those are
files under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found
through the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


class BenchError(Exception):
    """A cell that cannot run as declared: missing files, no chip, unknown
    device kind. The run exits non-zero and prints no result."""


# -- the cell's files, by name ---------------------------------------------
def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    benchmark: dict
    root: pathlib.Path

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list[dict]:
        return [m for m in self.benchmark["per_layer"] if self._mine(m)]

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = [w for w in bench["workloads"] if w["name"] == name]
    if not work:
        raise BenchError(f"no workload named {name!r} in BENCHMARK.json")
    w = work[0]
    confs = [c for c in bench["configs"] if c["name"] == w["config"]]
    if not confs:
        raise BenchError(f"workload {name!r} names unknown config {w['config']!r}")
    config = load_json(root / confs[0]["file"])
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.exists():
        raise BenchError(f"no traffic file {traffic_file.relative_to(root)}")
    return Cell(w, config, load_json(traffic_file), bench, root)


def load_module(path: pathlib.Path, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (driver and metric files carry dots and are
    not a package)."""
    if not path.exists():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell) -> ModuleType:
    return load_module(cell.root / "bench" / "drivers" / f"{cell.traffic['driver']}.py")


def metric_reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.root / "bench" / "metrics" / f"{metric}.py")


# -- device --------------------------------------------------------------
def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchError(
            f"no TPU: JAX found {d0.platform} devices; this benchmark runs on the chip only"
        )
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak_bytes(jax, chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks(device_kind: str, table: pathlib.Path = BENCH / "peaks.json") -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    data = load_json(table)
    if device_kind not in data["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in {table.name}")
    return data["devices"][device_kind]


# -- compile accounting ----------------------------------------------------
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass
class CompileClock:
    """Seconds in XLA backend compiles and the number of programs compiled
    or loaded from the persistent cache, from ``jax.monitoring``."""

    seconds: float = 0.0
    programs: int = 0

    def install(self, jax) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            self.programs += 1


def use_compile_cache(jax) -> None:
    """The program's persistent cache (fixed path inside the checkout, or
    ``JAX_COMPILATION_CACHE_DIR``), with every program cached so that a
    second run of a cell compiles nothing."""
    from repro.launch._env import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# -- set-up clock ------------------------------------------------------------
@dataclass
class SetupClock:
    """Set-up split into generation, compiling and warm-up, from process
    start to the window's start."""

    t0: float
    compile: CompileClock
    parts: dict = field(default_factory=dict)

    def part(self, name: str, seconds: float) -> None:
        self.parts[name] = self.parts.get(name, 0.0) + seconds


class Stopwatch:
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        return dt


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
