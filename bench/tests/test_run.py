"""Whole runs of the harness at rehearsal sizes on the CPU: the chip check,
discovery by name, the control and the planted faults.

``run.run(args, require_tpu=False)`` is the harness without its look for a
chip; everything else (the driver, the window, the reference, the checks)
runs as on the chip."""
import json
import shutil
import subprocess
import sys
import types

import pytest

from bench import harness
from bench.harness import BENCH, ROOT, load_module

run_mod = load_module(BENCH / "run.py", "bench_run_module")

CELLS = ["paper_r2m.bulk_1m", "paper_r2m.trickle_16k"]


def args(workload, seed=2**31 + 77, seconds=1.0, trace=0, control=0):
    return types.SimpleNamespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        control=control, rehearse=True,
    )


def run_cell(workload, **kw):
    return run_mod.run(args(workload, **kw), require_tpu=False)


def test_no_chip_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_rehearsal_last_line_is_no_chip_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "0.5", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".cache"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_correct_and_reports_its_metrics(workload):
    res = run_cell(workload)
    assert res["correct"], res["checks"]
    cell = harness.find_cell(workload)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert list(res)[-1] == "checks"
    assert res["checks"]["state_mismatch"]["value"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    # the reference one precision below the stated one (bfloat16 coin
    # quotient, float32 estimate) in the program's place
    res = run_cell(workload, seconds=2.0, control=1)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
