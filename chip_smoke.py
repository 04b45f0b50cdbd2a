"""Chip smoke test: drive the streaming triangle counter once on a TPU, through
the entry points a user calls, at the paper's width, and check the results.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the tenant-sharded bank on a 2x2 mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

One chip, all in this one process:
  device    the first JAX device must be a TPU;
  main      TriangleCountEngine(r=2^21, batch=2^20, chunk=4, backend auto)
            fed a seeded planted-triangle stream (exact tau known) through
            service.run_stream with report queries and one checkpoint, then
            a fresh engine restored from that checkpoint;
  checks    the state equals the same stream ingested with the "scan" ingest
            backend (the oracle); estimate() equals estimate(gather=True);
            the estimate is within 4 standard errors of tau; a small stream
            gives the same state on the TPU and on the host CPU device;
  served    ElasticServeLoop over 2 tenants at r=2^20: batches, ~100
            queries, one hot-add and one evict; a tenant's state equals a
            fixed engine's on the same stream.

``--chips 4`` runs only a 4-tenant bank on make_stream_mesh("tenants=2,
estimators=2") (plan banked_pjit_coordinated) at r=2^21 per tenant, batch
2^20, against a single-device engine, the gather oracle, and a per-device
shard count.

Any failed phase exits 1. The last line of a passing chip run is the JSON
object {"ok": true, "device": {...}}; without a TPU the script exits 2 and
prints no such line. ``--rehearse`` runs the same phases at tiny sizes on
whatever JAX finds (for example the CPU) and never prints it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, replace

ROOT = pathlib.Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    print(f"chip_smoke: no repro package under {ROOT / 'src'}", file=sys.stderr)
    sys.exit(1)
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (x64)
from repro.launch._env import use_compile_cache  # noqa: E402


@dataclass(frozen=True)
class Sizes:
    r: int  # estimators per tenant, main path
    s: int  # batch size, main path
    chunk: int  # batches per fused dispatch, main path
    batches: int  # full batches in the main stream
    serve_r: int  # estimators per tenant, served path
    serve_s: int  # batch size, served path
    serve_batches: int  # batches per tenant, served path
    small_r: int  # cross-platform check
    small_s: int


CHIP = Sizes(
    r=2**21, s=2**20, chunk=4, batches=8,
    serve_r=2**20, serve_s=2**16, serve_batches=4,
    small_r=65536, small_s=4096,
)
REHEARSAL = Sizes(
    r=2**12, s=2**10, chunk=4, batches=8,
    serve_r=2**10, serve_s=2**8, serve_batches=4,
    small_r=1024, small_s=256,
)
STATE_FIELDS = ("f1", "chi", "f2", "has_f3", "m_seen")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def check_same_state(a: dict, b: dict, what: str) -> None:
    diff = state_diff(a, b)
    check(not diff, what + (f" (differ: {diff})" if diff else ""))


class CompileClock:
    """Seconds spent in XLA backend compiles, from jax.monitoring."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def planted(n_edges: int, seed: int):
    """A seeded stream of exactly ``n_edges`` edges with a known triangle
    count: ``n_edges // 16`` disjoint triangles plus distinct noise edges
    between two further vertex classes A and B. The noise is bipartite, so
    it closes no triangle, and tau is exactly the number planted. Generated
    in bulk with numpy (the Python-loop generators in
    ``repro.data.graph_stream`` take minutes at this size)."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    n_tri = n_edges // 16
    v = 3 * np.arange(n_tri, dtype=np.int64)[:, None]
    tri = np.concatenate([v, v + 1, v, v + 2, v + 1, v + 2], axis=1)
    n_noise = n_edges - 3 * n_tri
    side = n_edges  # |A| = |B|: collisions among the noise draws stay rare
    keys = np.zeros((0,), np.int64)
    while keys.size < n_noise:
        draw = rng.integers(0, side * side, size=2 * n_noise, dtype=np.int64)
        keys = np.unique(np.concatenate([keys, draw]))
    keys = rng.permutation(keys)[:n_noise]
    a = 3 * n_tri + keys // side
    b = 3 * n_tri + side + keys % side
    edges = np.concatenate([tri.reshape(-1, 2), np.stack([a, b], axis=1)])
    edges = rng.permutation(edges).astype(np.int32)
    secs = time.perf_counter() - t0
    print(f"stream: m={len(edges)} tau={n_tri} (host generation {secs}s)")
    return edges, n_tri


def state_diff(a: dict, b: dict) -> str:
    """Which estimator fields differ between two snapshots, and in how many
    elements ('' when the states are identical)."""
    return ", ".join(
        f"{f}: {int(np.sum(a[f] != b[f]))} of {a[f].size}"
        for f in STATE_FIELDS
        if not np.array_equal(a[f], b[f])
    )


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def standard_error(snap: dict) -> float:
    """Standard error of the median-of-means estimate of tenant 0, from its
    coarse estimates X = chi * m on estimators holding a closed triangle:
    std(X)/sqrt(r) for the mean, times sqrt(pi/2) for taking the median of
    the group means."""
    x = np.where(
        snap["has_f3"][0],
        snap["chi"][0].astype(np.float64) * float(snap["m_seen"][0]),
        0.0,
    )
    return float(np.std(x) / np.sqrt(x.size) * np.sqrt(np.pi / 2))


def ingest(engine, edges, clock, **kw):
    from repro.data.graph_stream import batches
    from repro.engine import run_stream

    c0 = clock.seconds
    rep = run_stream(engine, batches(edges, engine.config.batch_size), **kw)
    compile_s = clock.seconds - c0
    print(
        f"  ingested {rep.edges} edges in {rep.batches} batches: "
        f"{rep.seconds}s wall, of which {compile_s}s compiling"
    )
    return rep


def main_path(sz: Sizes, clock: CompileClock) -> None:
    from repro.engine import EngineConfig, TriangleCountEngine, run_stream
    from repro.primitives.ingest import ingest_backend, set_ingest_backend
    from repro.primitives.search import multisearch_backend

    print(f"== main path: r={sz.r} s={sz.s} chunk={sz.chunk}")
    edges, tau = planted(sz.batches * sz.s, seed=0)
    cfg = EngineConfig(r=sz.r, batch_size=sz.s, chunk_size=sz.chunk)
    eng = TriangleCountEngine(cfg)
    print(
        f"  plan {eng.plan.name}, ingest backend {ingest_backend()}, "
        f"multisearch backend {multisearch_backend()}"
    )
    reports = []
    with tempfile.TemporaryDirectory() as ckpt:
        rep = ingest(
            eng, edges, clock, ckpt_dir=ckpt, report_every=sz.chunk,
            on_report=lambda step, est, seen: reports.append((step, est)),
        )
        check(rep.batches == sz.batches and eng.step == sz.batches,
              f"{sz.batches} batches ingested")
        check(len(reports) == sz.batches // sz.chunk and all(
            np.all(np.isfinite(e)) for _, e in reports
        ), f"{len(reports)} report queries answered, all finite")
        est = eng.estimate()
        gathered = eng.estimate(gather=True)
        check(np.array_equal(est, gathered),
              "estimate() == estimate(gather=True) bit for bit")
        snap = eng.bank_snapshot()
        restored = TriangleCountEngine(cfg)
        rep2 = run_stream(restored, iter(()), ckpt_dir=ckpt)
        check(rep2.resumed_from == sz.batches and rep2.batches == 0,
              f"checkpoint restored at step {rep2.resumed_from}")
        check(np.array_equal(restored.estimate(), est),
              "restored estimate identical")
        check_same_state(restored.bank_snapshot(), snap,
                         "restored state identical")
        del restored

    se = standard_error(snap)
    rel = abs(float(est[0]) - tau) / tau
    print(f"  estimate: {float(est[0])!r} true: {tau} rel.err: {rel!r} "
          f"SE/tau: {se / tau!r}")
    check(abs(float(est[0]) - tau) <= 4 * se, "estimate within 4 SE of tau")

    set_ingest_backend("scan")
    try:
        oracle = TriangleCountEngine(cfg)
        ingest(oracle, edges, clock)
        check_same_state(oracle.bank_snapshot(), snap,
                         "state == scan-backend oracle bit for bit")
    finally:
        set_ingest_backend("auto")
    print(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


def cross_platform(sz: Sizes, clock: CompileClock) -> None:
    from repro.engine import EngineConfig, TriangleCountEngine

    print(f"== cross-platform: r={sz.small_r} s={sz.small_s}, 4 batches")
    edges, _ = planted(4 * sz.small_s, seed=1)
    cfg = EngineConfig(r=sz.small_r, batch_size=sz.small_s)
    snaps = []
    for dev in (jax.devices()[0], jax.devices("cpu")[0]):
        with jax.default_device(dev):
            eng = TriangleCountEngine(cfg)
            ingest(eng, edges, clock)
            # the bank lives on the device it was built under
            placed = {d.platform for d in eng._state.f1.devices()}
            check(placed == {dev.platform}, f"state on {dev.platform}")
            snaps.append(eng.bank_snapshot())
    check_same_state(*snaps, "same state on both devices")


def served_path(sz: Sizes, clock: CompileClock) -> None:
    from repro.data.graph_stream import batches, erdos_renyi_stream
    from repro.engine import (
        ElasticBankEngine,
        ElasticServeLoop,
        EngineConfig,
        TriangleCountEngine,
    )

    print(f"== served path: 2 tenants r={sz.serve_r} s={sz.serve_s}")
    n = sz.serve_batches * sz.serve_s
    t0 = time.perf_counter()
    edges = erdos_renyi_stream(n // 4, n, seed=2)
    print(f"  stream: m={n} (host generation {time.perf_counter() - t0}s)")
    its = list(batches(edges, sz.serve_s))
    c0 = clock.seconds
    bank = ElasticBankEngine(sz.serve_r, sz.serve_s, capacity=4)
    print(f"  plan {bank.backend}, tier built in {clock.seconds - c0}s "
          "of compiling")
    futures = []
    t0 = time.perf_counter()
    loop = ElasticServeLoop(bank).start()
    try:
        loop.add_tenant("a", seed=1).result()
        loop.add_tenant("b", seed=2).result()
        for i, (W, nv) in enumerate(its):
            check(loop.submit("a", W, nv) and loop.submit("b", W, nv),
                  f"batch {i} accepted for both tenants")
            futures += [loop.query(t) for t in ("a", "b") for _ in range(12)]
            if i == 1:
                loop.add_tenant("c", seed=3).result()  # hot-add mid-stream
            elif i == 2:
                loop.submit("c", W, nv)
                futures.append(loop.query("c"))
                futures[-1].result(timeout=600)  # answered before the evict
            elif i == 3:
                loop.evict_tenant("c").result()
        answers = [f.result(timeout=600) for f in futures]
    finally:
        stats = loop.stop()
    snap_a = bank.snapshot_tenant("a")
    print(f"  {len(answers)} queries over {stats.ingest_dispatches} "
          f"dispatches in {time.perf_counter() - t0}s")
    check(stats.queries_answered == len(futures) and all(
        np.isfinite(a["estimate"]) for a in answers
    ), f"all {len(futures)} queries answered")
    check(stats.degraded_queries == 0 and all(
        a["stale_age"] == 0 for a in answers
    ), "no query degraded")
    check(bank.diag.hot_adds == 3 and bank.diag.evictions == 1,
          "one mid-stream hot-add and one evict applied")
    fixed = TriangleCountEngine(
        EngineConfig(r=sz.serve_r, batch_size=sz.serve_s, seeds=(1,))
    )
    for W, nv in its:
        fixed.ingest(W, nv)
    check_same_state(fixed.bank_snapshot(), snap_a,
                     "served tenant's state == fixed engine's bit for bit")


def four_chips(sz: Sizes, clock: CompileClock) -> None:
    from repro.engine import EngineConfig, TriangleCountEngine
    from repro.launch.mesh import make_stream_mesh

    T = 4
    print(f"== tenant-sharded bank: {T} tenants, r={sz.r} s={sz.s}")
    edges, _ = planted(2 * sz.s, seed=0)
    mesh = make_stream_mesh("tenants=2,estimators=2")
    cfg = EngineConfig(r=sz.r, batch_size=sz.s, n_tenants=T,
                       seeds=(11, 12, 13, 14))
    eng = TriangleCountEngine(cfg, mesh=mesh)
    print(f"  plan {eng.plan.name} on mesh {dict(mesh.shape)}")
    check(eng.plan.name == "banked_pjit_coordinated",
          "auto picked banked_pjit_coordinated")
    ingest(eng, edges, clock, report_every=1, on_report=lambda *a: None)
    # each chip must hold one distinct quarter of every estimator leaf
    for f in ("f1", "chi", "f2", "has_f3"):
        leaf = getattr(eng._state, f)
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == 4
              and len({str(s.index) for s in shards}) == 4
              and all(s.data.size * 4 == leaf.size for s in shards),
              f"{f}: 4 chips, a distinct quarter each")
    dev = eng.estimate()
    check(np.array_equal(dev, eng.estimate(gather=True)),
          "device-resident estimate == gather oracle bit for bit")
    snap = eng.bank_snapshot()
    ref = TriangleCountEngine(replace(cfg, backend="single"))
    ingest(ref, edges, clock)
    check_same_state(ref.bank_snapshot(), snap,
                     f"all {T} tenants == single-device engine bit for bit")
    print(f"  estimates: {[float(e) for e in dev]}")
    for d in jax.devices()[:4]:
        print(f"  {d}: peak_bytes_in_use {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; never prints the ok line")
    args = ap.parse_args(argv)

    use_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if not args.rehearse and d0.platform != "tpu":
        print("chip_smoke: no TPU found; this is not a chip run",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    sz = REHEARSAL if args.rehearse else CHIP
    clock = CompileClock()
    phases = (
        [four_chips] if args.chips == 4
        else [main_path, cross_platform, served_path]
    )
    t0 = time.perf_counter()
    for phase in phases:
        try:
            phase(sz, clock)
        except Exception:
            traceback.print_exc()
            print(f"FAILED: phase {phase.__name__}", file=sys.stderr)
            return 1
    print(f"all phases passed in {time.perf_counter() - t0}s "
          f"({clock.seconds}s compiling)")
    if args.rehearse:
        print("rehearsal only: no ok line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
