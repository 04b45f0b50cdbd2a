"""The program's own measurement points: the ``jax.named_scope`` stages of
the ingest programs (read from the compiled HLO's op metadata) and the
``repro.*`` host spans of ``run_stream`` (read from a profiler trace).
``bench/metrics`` reads both off a chip trace; these tests pin the names
those readers match."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data.graph_stream import batches, erdos_renyi_stream
from repro.engine import EngineConfig, TriangleCountEngine, run_stream

R, S = 64, 32
SCOPES = ("step1", "rng", "rank_all", "multisearch", "q1", "q2", "closing")
LOOP_SPANS = (
    "repro.stream.fetch", "repro.stream.validate", "repro.engine.stage",
    "repro.engine.dispatch", "repro.stream.report", "repro.engine.estimate",
    "repro.engine.wait",
)


def _engine(K: int) -> TriangleCountEngine:
    return TriangleCountEngine(
        EngineConfig(r=R, batch_size=S, chunk_size=K, seeds=(7,))
    )


def _scope_paths(hlo_text: str) -> list[list[str]]:
    """Each instruction's ``op_name`` as its list of scopes, with the
    transform wrappers JAX puts round a scope (``vmap(q1)``) taken off."""
    out = []
    for ln in hlo_text.splitlines():
        if 'op_name="' in ln:
            path = ln.split('op_name="', 1)[1].split('"', 1)[0]
            out.append(
                [re.sub(r"^(\w+\()+|\)+$", "", p) for p in path.split("/")]
            )
    return out


@pytest.mark.parametrize("K", [1, 4])
def test_ingest_programs_carry_stage_scopes(K):
    eng = _engine(K)
    T = eng.n_tenants
    if K == 1:
        program, module = eng.plan.build(eng.config, None), "jit_bulk_update"
        args = (
            eng._state, jnp.zeros((T, S, 2), jnp.int32),
            jnp.full((T,), S, jnp.int32), jnp.zeros((T, 2), jnp.uint32),
        )
    else:
        program = eng.plan.build_chunk(eng.config, None)
        module = "jit_chunk_update"
        args = (
            eng._state, jnp.zeros((T, K, S, 2), jnp.int32),
            jnp.full((T, K), S, jnp.int32), eng._root_keys, 0,
        )
    text = program.lower(*args).compile().as_text()
    assert text.startswith(f"HloModule {module},")
    paths = _scope_paths(text)
    for scope in SCOPES:
        assert any(scope in p for p in paths), scope
    # every search runs under its role
    for p in paths:
        if "multisearch" in p:
            assert {"q1", "q2", "closing"} & set(p), p


def _spans(trace_dir: pathlib.Path) -> list[tuple[str, dict, int]]:
    """``(name, stats, thread line)`` of every ``repro.*`` host span."""
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, dict(ev.stats), i))
    return out


def _profiled(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _spans(tmp_path / "trace")


@pytest.mark.parametrize("K", [1, 4])
def test_run_stream_writes_loop_spans(tmp_path, K):
    edges = erdos_renyi_stream(40, 9 * S, seed=3)  # 9 batches: K=4 has a tail
    eng = _engine(K)
    skip = 2 if K == 1 else 0
    if skip:  # resume past a prefix, as the benchmark's window does
        run_stream(eng, list(batches(edges, S))[:skip], prefetch_depth=1)
        eng.sync()
    ckpt = dict(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=3) if K == 1 else {}
    spans = _profiled(tmp_path, lambda: run_stream(
        eng, batches(edges, S), report_every=K, on_report=lambda *a: None,
        prefetch_depth=1, **ckpt,
    ))
    names = {n for n, _, _ in spans}
    for want in LOOP_SPANS + ("repro.prefetch.produce",):
        assert want in names, want
    assert ("repro.stream.checkpoint" in names) == (K == 1)

    dispatch = [st for n, st, _ in spans if n == "repro.engine.dispatch"]
    n_chunks, tail = divmod(9 - skip, K)
    assert len(dispatch) == n_chunks + (tail if K > 1 else 0)
    assert [st["step"] for st in dispatch] == (
        list(range(skip, 9)) if K == 1 else [0, 4, 8]
    )
    fetch = [st for n, st, _ in spans if n == "repro.stream.fetch"]
    assert [st["skipped"] for st in fetch[:skip + 1]] == [1] * skip + [0]
    assert sum(st["skipped"] for st in fetch) == skip
    # the loop's spans share one thread; the producer runs on its own
    loop = {ln for n, _, ln in spans
            if n.startswith(("repro.stream.", "repro.engine."))}
    produce = {ln for n, _, ln in spans if n == "repro.prefetch.produce"}
    assert len(loop) == 1 and not loop & produce

    # the spans time the work; the state is what an untraced loop makes
    ref = _engine(K)
    run_stream(ref, batches(edges, S), report_every=K, on_report=lambda *a: None)
    for f, a in ref.snapshot().items():
        np.testing.assert_array_equal(eng.snapshot()[f], a, err_msg=f)
