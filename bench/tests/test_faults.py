"""The timed path broken underneath: each fault a cell can have must turn
``correct`` false. Faults are planted in the program (monkeypatched), the
harness runs as on the chip except for its look for a chip. One chip, so
no exchange between chips to leave out."""
import types

import pytest

from bench.harness import BENCH, load_module
from repro.engine import TriangleCountEngine

run_mod = load_module(BENCH / "run.py", "bench_run_module_faults")

CELLS = ["paper_r2m.bulk_1m", "paper_r2m.trickle_16k"]


def run_cell(workload):
    args = types.SimpleNamespace(
        workload=workload, seed=2**31 + 99, seconds=1.0, trace=0, control=0,
        rehearse=True,
    )
    return run_mod.run(args, require_tpu=False)


def _patch_engine_programs(monkeypatch, wrap_update, wrap_chunk):
    init = TriangleCountEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        self._update = wrap_update(self._update)
        if self._update_chunk is not None:
            self._update_chunk = wrap_chunk(self._update_chunk)

    monkeypatch.setattr(TriangleCountEngine, "__init__", patched)


def state_unchanged(monkeypatch):
    keep = lambda f: (lambda st, *a: st)
    _patch_engine_programs(monkeypatch, keep, keep)


def half_batch(monkeypatch):
    def halve(f):
        return lambda st, W, nv, *a: f(st, W, nv // 2, *a)

    _patch_engine_programs(monkeypatch, halve, halve)


def answer_altered(monkeypatch):
    estimate = TriangleCountEngine.estimate

    def altered(self, *a, **kw):
        return estimate(self, *a, **kw) * 1.001 + 1.0

    monkeypatch.setattr(TriangleCountEngine, "estimate", altered)


FAULTS = [state_unchanged, half_batch, answer_altered]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    res = run_cell(workload)
    assert not res["correct"], res["checks"]


def test_unpatched_program_is_correct():
    assert run_cell("paper_r2m.trickle_16k")["correct"]
