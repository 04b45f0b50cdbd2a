"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``; the traffic file names the driver in ``bench/drivers``
that plays it, and each per-layer metric is read by ``bench/metrics/<name>.py``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. A metric reader gets
the run's record: the counters, the trace's reduction (``bench/tracing.py``
``reduce``) and the whole trace as loaded (every plane, line and event), so
a new reader can reduce the trace itself.

The run needs the chip: without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result. ``--rehearse`` runs the cell at
the tiny sizes in the files' ``rehearse`` blocks on whatever JAX finds, and
its last line says that it is no chip result. ``--control 1`` puts the
reference, computed one precision lower, in the program's place (the
check's control).
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.harness import BenchError, log  # noqa: E402


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Context:
    """What a driver gets: the cell, the run's arguments, and the window,
    trace, memory and check bookkeeping."""

    def __init__(self, cell, args, jax, compile_clock):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.control = bool(args.control)
        self.jax = jax
        self.setup = harness.SetupClock(_T_START, compile_clock)
        self.counters: dict = {}
        self.checks: dict = {}
        self.memory_peak = None
        self.window = None  # (start, end) perf_counter seconds
        self.trace_dir = harness.CACHE / "trace" / cell.name
        self._tracing = False
        self._span = None

    log = staticmethod(log)

    def open_window(self) -> float:
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            self.jax.profiler.start_trace(str(self.trace_dir))
            self._span = self.jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self._tracing = True
        t0 = time.perf_counter()
        self.window = (t0, None)
        return t0

    def close_window(self) -> float:
        t1 = time.perf_counter()
        self.window = (self.window[0], t1)
        if self._tracing:
            self._span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self._tracing = False
        return t1

    def read_memory(self) -> None:
        self.memory_peak = harness.memory_peak_bytes(self.jax, self.cell.workload["chips"])

    def check(self, name: str, value, detail=None) -> None:
        limit = self.cell.config["limits"][name]
        self.checks[name] = {"value": value, "limit": limit}
        if detail is not None:
            log(f"check {name}: {detail}")


def per_layer(cell, record: dict) -> dict:
    out = {}
    for m in cell.per_layer():
        value = harness.metric_reader(cell, m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, require_tpu: bool = True, root: pathlib.Path = ROOT) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    cell = harness.find_cell(args.workload, root)
    if args.rehearse:
        cell.config = merged(cell.config, cell.config.get("rehearse", {}))
        cell.traffic = merged(cell.traffic, cell.traffic.get("rehearse", {}))
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"no program under {root / 'src'}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax

    import repro  # noqa: F401  (the program turns on 64-bit types)

    harness.use_compile_cache(jax)
    device = harness.device_info(jax, cell.workload["chips"], require_tpu)
    clock = harness.CompileClock().install(jax)
    ctx = Context(cell, args, jax, clock)
    out = harness.driver(cell).run(ctx)

    t0, t1 = ctx.window
    setup = {
        "setup_s": t0 - _T_START, "compile_s": clock.seconds, **ctx.setup.parts,
        "programs_compiled_or_loaded": clock.programs,
    }
    log(f"setup: {setup}")
    log(f"counters: {ctx.counters}")
    checks = ctx.checks
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    correct = correct and ctx.counters.get("compiles_in_window", 0) == 0
    result = {
        "correct": correct,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {},
        "device": {**device, "memory_peak_bytes": ctx.memory_peak},
    }
    if args.trace:
        from bench import tracing

        trace = tracing.load(ctx.trace_dir)
        red = tracing.reduce(trace, harness.load_json(harness.BENCH / "modules.json"))
        if red is None and require_tpu:
            raise BenchError("the trace of the window holds no TPU device plane")
        record = {
            "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
            "counters": ctx.counters, "trace": red, "raw_trace": trace,
            "device": device,
            "peaks": harness.peaks(device["kind"]) if require_tpu else None,
        }
        result["metrics"] = per_layer(cell, record)
        if red is not None:
            result["device"]["busy_s"] = red["busy_s"]
            result["device"]["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]
            }
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        values = {**out["metrics"], "setup_s": setup["setup_s"]}
        result["metrics"] = {k: {"value": values[k], "unit": u} for k, u in units.items()}
    for name, c in checks.items():
        log(f"{name} {c['value']} limit {c['limit']}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        result = run(args, require_tpu=not args.rehearse)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    except Exception:  # noqa: BLE001 — a failed run prints its cause, no result
        traceback.print_exc()
        return 1
    line = json.dumps(result)
    if args.rehearse:
        print(line)
        print("rehearsal only: not a chip result")
        return 0
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
