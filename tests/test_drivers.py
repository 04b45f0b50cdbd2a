"""End-to-end driver tests (subprocess, small sizes): the streaming counter
with checkpoint-resume, and the LM trainer."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
ENV = {
    "PYTHONPATH": str(ROOT / "src"),
    "PATH": "/usr/bin:/bin:/usr/local/bin",
    "JAX_PLATFORMS": "cpu",
    # the CLIs turn on the persistent compilation cache; keep test runs
    # from writing one into the checkout
    "JAX_ENABLE_COMPILATION_CACHE": "false",
}


def run(args, timeout=420):
    return subprocess.run(
        [sys.executable, "-m"] + args,
        capture_output=True, text=True, timeout=timeout, env=ENV, cwd=ROOT,
    )


@pytest.mark.slow
def test_stream_driver_accuracy_and_resume(tmp_path):
    # The run is bit-deterministic (counter-based RNG), so the rel.err below is
    # a fixed number per seed, not a flaky draw; --seed selects BOTH the BA
    # graph and the RNG stream. The 10% bound must sit at >= 4 standard
    # errors, or a change of random stream alone can cross it: at r=50k the
    # rel.err over --seed 0..7 has an RMS of 12%, and --seed 2 moved from
    # 3.66% to 10.76% when jax's default threefry became partitionable. At
    # r=2^21 the RMS over --seed 0..7 is 2.0% (10% = 5 SE); --seed 2 prints
    # 1.44%.
    base = [
        "repro.launch.stream", "--graph", "ba", "--nodes", "2000",
        "--estimators", str(2**21), "--batch", "2048", "--seed", "2",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
    ]
    p1 = run(base)
    assert p1.returncode == 0, p1.stderr
    line = [l for l in p1.stdout.splitlines() if "rel.err" in l][0]
    err = float(line.split("rel.err:")[1].strip().rstrip("%")) / 100
    assert err < 0.10, line
    # resume: a second run restores from the final manifest and reports the
    # same estimate (counter-based RNG => deterministic)
    p2 = run(base)
    assert p2.returncode == 0, p2.stderr
    est1 = [l for l in p1.stdout.splitlines() if l.startswith("estimate")][0]
    est2 = [l for l in p2.stdout.splitlines() if l.startswith("estimate")][0]
    assert est1 == est2


@pytest.mark.slow
def test_stream_driver_tenant_sharded_matches_single(tmp_path):
    """The --mesh CLI path end to end: a tenant-sharded bank over 4 forced
    CPU devices prints the same estimates as the default single plan (the
    counter-based RNG makes the plans interchangeable; docs/scaling.md)."""
    base = [
        "repro.launch.stream", "--graph", "er", "--nodes", "60",
        "--edges", "500", "--estimators", "512", "--batch", "32",
        "--tenants", "4", "--ckpt-every", "0",
    ]
    p1 = run(base)
    assert p1.returncode == 0, p1.stderr
    p2 = run(base + ["--host-devices", "4",
                     "--mesh", "tenants=2,estimators=2"])
    assert p2.returncode == 0, p2.stderr
    assert "plan banked_pjit_coordinated" in p2.stdout, p2.stdout
    ests1 = [l for l in p1.stdout.splitlines() if l.startswith("estimate")]
    ests2 = [l for l in p2.stdout.splitlines() if l.startswith("estimate")]
    assert ests1 == ests2 and len(ests1) == 4


@pytest.mark.slow
def test_lm_train_driver_smoke(tmp_path):
    # fresh ckpt dir per run: the trainer auto-resumes from an existing one,
    # which would skip all steps on a re-run (that behavior is covered by
    # test_stream_driver_accuracy_and_resume)
    p = run([
        "repro.launch.train", "--smoke", "--steps", "30", "--batch", "4",
        "--seq", "32", "--corpus-tokens", "20000", "--lr", "1e-2",
        "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "0",
    ])
    assert p.returncode == 0, p.stderr
    out = p.stdout
    first = float(out.split("first logged =")[1].split()[0])
    last = float(out.split("last =")[1].split()[0])
    assert last < first, out  # loss decreased


@pytest.mark.slow
def test_dryrun_single_cell_cli(tmp_path):
    """The dry-run CLI works end to end for one small cell (512 devices)."""
    p = run([
        "repro.launch.dryrun", "--arch", "gat-cora", "--shape", "molecule",
        "--out-dir", str(tmp_path),
    ], timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    rec = json.loads((tmp_path / "gat-cora__molecule__pod.json").read_text())
    assert rec["ok"] and rec["chips"] == 256
    assert rec["cost"]["flops"] > 0
