"""The reduction from a trace to the per-layer numbers."""
import pytest

from bench import tracing
from bench.harness import BENCH, load_json, load_module

TABLE = load_json(BENCH / "modules.json")
MS = 1e6  # nanoseconds per millisecond


def synthetic():
    """A 100 ms window: two ingest executions and one estimate, with ops,
    and host spans around them."""
    host = tracing.Plane("/host:CPU", [tracing.Line("python3", [
        ["bench.window", 0.0, 100 * MS],
        ["bench.run_stream", 0.0, 80 * MS],
        ["bench.estimate", 85 * MS, 15 * MS],
    ])])
    dev = tracing.Plane("/device:TPU:0", [
        tracing.Line("XLA Modules", [
            ["jit_bulk_update(123)", 10 * MS, 30 * MS],
            ["jit_bulk_update(123)", 45 * MS, 30 * MS],
            ["jit__lambda(9)", 90 * MS, 5 * MS],
            ["jit_before_window(1)", -20 * MS, 10 * MS],
        ]),
        tracing.Line("XLA Ops", [
            ["sort.1", 10 * MS, 10 * MS],
            ["fusion.2", 20 * MS, 20 * MS],
            ["sort.1", 45 * MS, 12 * MS],
            ["fusion.2", 57 * MS, 18 * MS],
            ["reduce.3", 90 * MS, 5 * MS],
            ["copy.9", -20 * MS, 10 * MS],
        ]),
    ])
    return tracing.Trace([host, dev])


def test_busy_roles_and_sorts():
    red = tracing.reduce(synthetic(), TABLE)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.065)
    ingest = red["roles"]["ingest"]
    assert ingest["executions"] == 2 and ingest["device_s"] == pytest.approx(0.06)
    assert ingest["sort_s"] == pytest.approx(0.022)
    # the estimate is in no role of the table; the module before the window
    # is cut away
    assert red["roles"]["other"]["device_s"] == pytest.approx(0.005)
    assert red["roles"]["other"]["executions"] == 1
    assert red["top_ops"][0] == ["fusion.2", pytest.approx(0.038)]


def test_idle_gaps_are_labelled_by_the_host_span():
    red = tracing.reduce(synthetic(), TABLE)
    gaps = dict((round(s * 1e3, 6), label) for label, s in red["idle_gaps"])
    # 0-10 ms and 40-45 ms and 75-80 ms inside run_stream; 80-85 outside
    # any inner span; 85-90 and 95-100 inside the estimate
    assert gaps[10.0] == "bench.run_stream"
    assert gaps[5.0] in ("bench.run_stream", "bench.estimate", "outside harness spans")
    labels = [label for label, _ in red["idle_gaps"]]
    assert "outside harness spans" in labels and "bench.estimate" in labels
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(0.035)


def test_no_device_plane_reads_nothing():
    t = synthetic()
    t.planes = t.planes[:1]
    assert tracing.reduce(t, TABLE) is None


def test_union_and_gaps():
    assert tracing.union_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracing.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_unknown_module_is_other():
    assert tracing.module_role("jit_something_new(1)", TABLE["roles"]) == "other"
    assert tracing.module_role("jit_chunk_update(5)", TABLE["roles"]) == "ingest"


def test_metric_readers_on_the_reduction():
    red = tracing.reduce(synthetic(), TABLE)
    record = {"trace": red, "counters": {"batches_per_dispatch": 1, "r": 1000, "batch": 100},
              "peaks": {"hbm_bytes_per_s": 819e9}}
    read = lambda name: load_module(BENCH / "metrics" / f"{name}.py").read(record)
    assert read("idle_share.bulk") == pytest.approx(35.0)
    assert read("ingest_ms_per_batch") == pytest.approx(30.0)
    assert read("sort_share") == pytest.approx(100 * 22 / 60)
    floor_s = (40 * 100 + 42 * 1000) / 819e9
    assert read("ingest_roofline") == pytest.approx(100 * floor_s / 0.03)


def recorded():
    """A trace recorded on one TPU v5 lite: the first 0.12 s of a
    rehearsal-sized ``paper_r2m.bulk_1m`` window (``--rehearse --trace 1``),
    the device's module and op lines and the harness's spans."""
    import gzip
    import json

    with gzip.open(BENCH / "tests" / "data" / "tpu_v5e_tiny_trace.json.gz", "rt") as f:
        return tracing.Trace.from_json(json.load(f))


def test_recorded_trace_reduction():
    red = tracing.reduce(recorded(), TABLE)
    assert red["window_s"] == pytest.approx(0.12)
    assert 0 < red["busy_s"] < red["window_s"]
    ingest = red["roles"]["ingest"]
    assert ingest["executions"] == 8  # jit_bulk_update, one per batch
    # the rest: the reports' estimates (7 of jit__lambda) and the small key
    # and cast programs around the batches
    assert red["roles"]["other"]["executions"] == 23
    # the structure build's sorts are found inside the ingest program
    assert 0 < ingest["sort_s"] < 0.05 * ingest["device_s"]
    # op names are the HLO names, nested ops named under their while loop
    names = [name for name, _ in red["top_ops"]]
    assert names[0].startswith("while.") and "/fusion." in names[0]
    # self times add up to no more than the busy time
    assert sum(s for _, s in red["top_ops"]) <= red["busy_s"]
    assert {label for label, _ in red["idle_gaps"]} <= {
        "bench.run_stream", "bench.estimate", "outside harness spans"}


def test_recorded_trace_metrics():
    red = tracing.reduce(recorded(), TABLE)
    record = {"trace": red, "peaks": {"hbm_bytes_per_s": 819e9},
              "counters": {"batches_per_dispatch": 1, "r": 4096, "batch": 1024}}
    read = lambda name: load_module(BENCH / "metrics" / f"{name}.py").read(record)
    ms = read("ingest_ms_per_batch")
    assert 5 < ms < 10
    assert 0 < read("ingest_roofline") < 100
    assert 0 < read("sort_share") < 5
    assert 0 < read("idle_share.bulk") < 100


def test_load_keeps_the_whole_trace(tmp_path):
    # spans the program or JAX itself records, which no reader of today
    # looks at, reach the readers as well as the harness's own
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("program.dispatch"):
            jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = tracing.load(tmp_path)
    host = [p for p in trace.planes if p.name == tracing.HOST_PLANE]
    names = {ev[0] for p in host for ln in p.lines for ev in ln.events}
    assert {"bench.window", "program.dispatch"} <= names
    assert len(trace.planes) > 1
