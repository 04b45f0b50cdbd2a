"""Closed-loop ingest through the production loop: one ``run_stream`` on a
``TriangleCountEngine`` over the window, fed from the cell's Kronecker
stream as fast as it takes batches, with a rolling report every
``report_every`` batches and one ``estimate()`` at the end.

The report is what closes the loop: it waits for the device to finish the
batches before it, so the host never runs more than ``report_every`` plus
``prefetch_depth`` batches ahead of the chip. The source stops at the first
report boundary after ``--seconds``; the window closes when ``run_stream``
has ingested every batch given to it and the final estimate is on the host.
``edge_rate`` is every edge ingested in the window over the window's length.

``run_stream`` treats its iterator as the whole stream and skips the prefix
the engine has already taken, so the window's iterator starts again at the
first warm-up batch.

Correctness: the engine's state after the window (warm-up batches included)
is compared element for element with the plain reference replaying the
same stream from the same seed, leaving out only what rests on a coin that
ties with its quotient (``bench/reference/nbsi.py``), and the final
estimate with the reference's estimate of the same state.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import harness
from bench.reference import nbsi
from bench.traffic import kronecker


def _engine_seed(seed: int) -> int:
    return int(np.random.default_rng([seed, 1]).integers(0, 2**31 - 1))


def source(stream, s: int, every: int, n_batches=None, until=None,
           clock=time.perf_counter):
    """``(W, n_valid)`` batches of ``stream`` from its first edge: ``n_batches``
    of them, or whole report periods of ``every`` batches until ``clock()``
    passes ``until`` (the window-closing rule: the source is looked at only
    at report boundaries, so the window ends on a report)."""
    i = 0
    while (n_batches is None or i < n_batches) and not (
        until is not None and i % every == 0 and clock() >= until
    ):
        yield stream.take(i * s, s), s
        i += 1


def run(ctx) -> dict:
    import jax

    from repro.engine import EngineConfig, TriangleCountEngine, run_stream

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    r, s, K = cfg["r"], tr["batch"], tr["chunk_size"]

    sw = harness.Stopwatch()
    pool = kronecker.pool(cfg)
    stream = kronecker.stream(pool, 1 << cfg["graph_scale"], ctx.seed)
    ctx.setup.part("generate_s", sw.lap())

    seed = _engine_seed(ctx.seed)
    engine = TriangleCountEngine(
        EngineConfig(
            r=r, batch_size=s, chunk_size=K, seeds=(seed,),
            groups=cfg["groups"], scheme=cfg["scheme"], backend=cfg["plan"],
        )
    )
    every, depth = tr["report_every"], tr["prefetch_depth"]
    if every % K or tr["warmup_batches"] % every:
        raise harness.BenchError(
            "report_every must divide warmup_batches and be a multiple of chunk_size"
        )
    reports = []

    def feed(n_batches=None, until=None):
        return source(stream, s, every, n_batches, until)

    def on_report(step, estimates, seen):
        reports.append(step)

    # warm-up: the window's programs (ingest and the report's estimate)
    run_stream(engine, feed(n_batches=tr["warmup_batches"]), report_every=every,
               on_report=on_report, prefetch_depth=depth)
    engine.estimate()
    ctx.setup.part("warmup_s", sw.lap())

    compiles0 = ctx.setup.compile.programs
    step0, edges0 = engine.step, engine.diag.edges_ingested
    t0 = ctx.open_window()
    with jax.profiler.TraceAnnotation("bench.run_stream"):
        run_stream(engine, feed(until=t0 + ctx.seconds), report_every=every,
                   on_report=on_report, prefetch_depth=depth)
    with jax.profiler.TraceAnnotation("bench.estimate"):
        est = float(np.asarray(engine.estimate())[0])
    t1 = ctx.close_window()

    edges = engine.diag.edges_ingested - edges0
    batches = engine.step - step0
    ctx.log(
        f"window: {t1 - t0}s, {batches} batches, {edges} edges, "
        f"{len(reports)} reports, compiles in window "
        f"{ctx.setup.compile.programs - compiles0}"
    )
    ctx.counters.update(
        batches=batches, edges=edges, dispatches=batches // K,
        batches_per_dispatch=K, batch=s, r=r,
        compiles_in_window=ctx.setup.compile.programs - compiles0,
    )
    n_fed = engine.step
    ctx.read_memory()
    snap = engine.bank_snapshot()
    del engine
    sw.lap()

    # the plain reference over every batch the engine took, warm-up included
    ref = nbsi.State.fresh(r)
    draws = nbsi.Draws(seed, r)
    low = nbsi.State.fresh(r) if ctx.control else None
    for i in range(n_fed):
        W = stream.take(i * s, s)
        ref = nbsi.update(ref, W, draws, i)
        if low is not None:
            low = nbsi.update(low, W, draws, i, coin_dtype=nbsi.BFLOAT16)
    if low is not None:
        # the control: the reference one precision below the stated one
        # (bfloat16 coin quotient, float32 estimate) in the program's place
        snap = {f: getattr(low, f) for f in nbsi.FIELDS}
        est = nbsi.estimate(low, cfg["groups"], np.float32)
    ctx.log(f"reference: {sw.lap()}s")
    diff = nbsi.mismatches(snap, ref)
    raw = nbsi.mismatches(snap, dataclasses.replace(ref, unsure=ref.unsure & False))
    ctx.log(f"coin ties: {int(ref.unsure.sum())} estimators unsure at the end; "
            f"mismatches with them counted: {raw}")
    ctx.check("state_mismatch", sum(diff.values()), detail=diff)
    want = nbsi.estimate(nbsi.settled(snap, ref), cfg["groups"])
    ctx.check("estimate_rel_gap", abs(est - want) / max(abs(want), 1.0),
              detail={"program": est, "reference": want})

    return {
        "attempted": batches,
        "failed": 0,
        "metrics": {"edge_rate": edges / (t1 - t0)},
    }
