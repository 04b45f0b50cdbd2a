"""A configuration, a traffic mix and per-layer metrics that the harness
was not written with: new files plus new entries in BENCHMARK.json, and no
edit of any file it has."""
import gzip
import json
import shutil
import types

import pytest

from bench import tracing
from bench.harness import BENCH, ROOT, load_module

run_mod = load_module(BENCH / "run.py", "bench_run_module_discovery")


def new_cell(tmp_path):
    """A checkout with a new configuration, traffic mix and cell, and a
    counter-reading metric: new files and new entries only."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".cache"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = json.loads((BENCH / "configs" / "paper_r2m.json").read_text())
    config.update(name="wide_tiny", r=2048, graph_scale=9, draws=16 * 2**9)
    (tmp_path / "bench/configs/wide_tiny.json").write_text(json.dumps(config))
    (tmp_path / "bench/traffic/pairs_512.json").write_text(json.dumps({
        "driver": "stream", "batch": 512, "chunk_size": 2, "report_every": 4,
        "prefetch_depth": 2, "warmup_batches": 4,
    }))
    (tmp_path / "bench/metrics/dispatches_seen.py").write_text(
        "def read(record):\n    return record['counters']['dispatches']\n"
    )
    bench["configs"].append({
        "name": "wide_tiny", "source": "https://arxiv.org/abs/1308.2166",
        "file": "bench/configs/wide_tiny.json", "reduced": [], "why": "test",
    })
    bench["workloads"].append({
        "name": "wide_tiny.pairs_512", "config": "wide_tiny", "traffic": "pairs_512",
        "chips": 1, "why": "test",
    })
    bench["end_to_end"][0]["workloads"].append("wide_tiny.pairs_512")
    bench["per_layer"].append({
        "name": "dispatches_seen", "unit": "dispatches", "better": "higher",
        "source": "program_counter", "layer": "ingest program", "moves": "edge_rate",
        "workloads": ["wide_tiny.pairs_512"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def args(trace):
    return types.SimpleNamespace(
        workload="wide_tiny.pairs_512", seed=5, seconds=0.5, trace=trace,
        control=0, rehearse=False,
    )


def test_new_files_are_found_by_name(tmp_path):
    new_cell(tmp_path)
    res = run_mod.run(args(0), require_tpu=False, root=tmp_path)
    assert res["correct"]
    assert set(res["metrics"]) == {"edge_rate", "setup_s"}
    traced = run_mod.run(args(1), require_tpu=False, root=tmp_path)
    assert traced["metrics"]["dispatches_seen"]["value"] > 0
    # metrics of other cells are not asked of this one
    assert "batches_per_dispatch" not in traced["metrics"]


WHILE_S = """
def read(record):
    \"\"\"Device seconds in the HLO while loops, read off the whole trace.\"\"\"
    total = 0.0
    for plane in record["raw_trace"].planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    total += sum(d for n, s, d in line.events
                                 if n.lstrip("%").startswith("while."))
    return total / 1e9 if total else None
"""


def test_a_new_metric_reads_the_whole_trace(tmp_path, monkeypatch):
    # a reader of its own file reduces the loaded trace itself: here the
    # trace recorded on a TPU v5 lite, in place of the CPU run's
    bench = new_cell(tmp_path)
    (tmp_path / "bench/metrics/while_s.py").write_text(WHILE_S)
    bench["per_layer"].append({
        "name": "while_s", "unit": "s", "better": "lower", "source": "device_trace",
        "layer": "multisearch", "moves": "edge_rate", "workloads": ["wide_tiny.pairs_512"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with gzip.open(BENCH / "tests" / "data" / "tpu_v5e_tiny_trace.json.gz", "rt") as f:
        data = json.load(f)
    monkeypatch.setattr(tracing, "load", lambda _dir: tracing.Trace.from_json(data))

    traced = run_mod.run(args(1), require_tpu=False, root=tmp_path)
    want = sum(d for n, s, d in data["planes"][0]["lines"][1]["events"]
               if n.startswith("while.")) / 1e9
    assert traced["metrics"]["while_s"] == {"value": pytest.approx(want), "unit": "s"}
    assert 0 < want < traced["device"]["window_s"]
