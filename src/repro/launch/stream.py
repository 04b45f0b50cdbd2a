"""Streaming triangle-count driver: a thin CLI over TriangleCountEngine.

Reads/generates an edge stream and drains it through the engine service loop
(prefetched ingestion, periodic snapshots, auto-resume), then reports the
estimate, throughput, and accuracy when the true count is known. With
``--tenants N`` the same stream is counted by N independent estimator banks
(accuracy tiers / seed replicas) in one shared jit program; tenant 0 always
reproduces the single-tenant run bit-for-bit.

  PYTHONPATH=src python -m repro.launch.stream --graph ba --nodes 2000 \
      --estimators 100000 --batch 4096
  PYTHONPATH=src python -m repro.launch.stream --graph ba --tenants 4
  PYTHONPATH=src python -m repro.launch.stream --tenants 4 \
      --host-devices 4 --mesh tenants=2,estimators=2   # tenant-sharded bank
  PYTHONPATH=src python -m repro.launch.stream --scheme local --pools 4 \
      --graph er --nodes 100 --edges 1500              # per-vertex counts
"""
from __future__ import annotations

import argparse
import sys

from repro.launch._env import apply_host_devices, use_compile_cache

if __name__ == "__main__":
    # must run before any jax device query (see repro.launch._env); guarded
    # so merely importing this module never mutates the environment
    apply_host_devices(sys.argv)
    use_compile_cache()

import repro  # noqa: F401,E402
from repro.core.sequential import count_triangles, local_triangle_counts
from repro.data.graph_stream import (
    barabasi_albert_stream,
    batches,
    churn_stream,
    dynamic_live_edges,
    erdos_renyi_stream,
    planted_triangle_stream,
    signed_batches,
)
from repro.engine import (
    EngineConfig,
    ResilienceConfig,
    RetryPolicy,
    TriangleCountEngine,
    install_fault_plan,
    parse_fault_plan,
    run_signed_stream,
    run_stream,
)
from repro.launch.mesh import make_stream_mesh


def make_stream(args):
    if args.graph == "ba":
        edges = barabasi_albert_stream(args.nodes, args.degree, seed=args.seed)
        tau = count_triangles(edges) if args.nodes <= 20000 else None
    elif args.graph == "er":
        edges = erdos_renyi_stream(args.nodes, args.edges, seed=args.seed)
        tau = count_triangles(edges) if args.edges <= 2_000_000 else None
    else:
        edges, tau = planted_triangle_stream(
            args.triangles, args.edges, args.nodes, seed=args.seed
        )
    return edges, tau


def scheme_args(args) -> dict:
    """EngineConfig scheme kwargs from CLI flags (shared by both drivers)."""
    scheme = getattr(args, "scheme", "global")
    params = None
    if scheme == "local":
        params = (
            ("n_pools", getattr(args, "pools", 1)),
            ("n_vertices", getattr(args, "vertices", 0) or args.nodes),
        )
    return {"scheme": scheme, "scheme_params": params}


def build_engine(args) -> TriangleCountEngine:
    mesh = make_stream_mesh(getattr(args, "mesh", "") or "")
    engine = TriangleCountEngine(
        EngineConfig(
            r=args.estimators,
            batch_size=args.batch,
            n_tenants=args.tenants,
            groups=args.groups,
            seeds=tuple(args.seed + t for t in range(args.tenants)),
            backend=args.backend,
            tenant_axis=getattr(args, "tenant_axis", "tenants"),
            chunk_size=getattr(args, "chunk", 1),
            window=getattr(args, "window", 0),
            decay=getattr(args, "decay", 0.0),
            **scheme_args(args),
        ),
        mesh=mesh,
    )
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} -> plan {engine.plan.name}", flush=True)
    return engine


def add_dynamic_flags(ap) -> None:
    """Turnstile/window flags shared by the stream drivers."""
    ap.add_argument("--deletions", type=float, default=0.0,
                    help="turnstile churn: each edge is deleted later in the "
                         "stream with this probability (0 = insertion-only)")
    ap.add_argument("--window", type=int, default=0,
                    help="count-based sliding window: keep only the most "
                         "recent N inserted edges live (0 = unbounded)")
    ap.add_argument("--decay", type=float, default=0.0,
                    help="exponential decay: mean edge lifetime in "
                         "insertions, > 1 (0 = off; excludes --window)")


def make_dynamic_stream(args, edges):
    """(signed stream, live edge set) for the dynamic flags; the live set is
    the exact ground truth after windows/decay — what the estimate chases."""
    if args.deletions:
        stream = churn_stream(edges, args.deletions, seed=args.seed + 1)
    else:  # window/decay only: all-insert signed stream
        import numpy as np

        stream = np.concatenate(
            [edges, np.ones((len(edges), 1), np.int32)], axis=1
        )
    live = dynamic_live_edges(
        stream, window=args.window, decay=args.decay, seed=args.seed
    )
    return stream, live


def add_resilience_flags(ap) -> None:
    """Chaos/resilience flags shared by both stream drivers
    (docs/robustness.md)."""
    ap.add_argument("--fault-plan", default="",
                    help="inject deterministic faults: comma-joined "
                         "site:kind@AT[xTIMES][~DELAY_S] specs, e.g. "
                         "'engine.ingest:raise@3x2,checkpoint.write:torn@1' "
                         "(sites/kinds: repro.engine.faults)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="bounded retries (exponential backoff + jitter) for "
                         "transient source/ingest/stage faults")
    ap.add_argument("--retry-base", type=float, default=0.02,
                    help="base backoff seconds (doubles per attempt)")
    ap.add_argument("--query-timeout", type=float, default=0.0,
                    help="per-query wall-clock bound on the device-resident "
                         "estimate; on expiry the answer degrades to the "
                         "gather oracle (0 = unbounded)")
    ap.add_argument("--backpressure", type=int, default=0,
                    help="answer report queries from the (stale, tagged) "
                         "estimate cache when the prefetch backlog reaches "
                         "this depth (0 = always query fresh)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip batch validation/quarantine (trusted source)")
    ap.add_argument("--diag-json", default="",
                    help="dump engine diag + resilience counters to this "
                         "JSON file at exit (the CI chaos artifact)")


def resilience_from_args(args) -> ResilienceConfig:
    return ResilienceConfig(
        retry=RetryPolicy(
            max_retries=args.max_retries,
            base_s=args.retry_base,
            seed=args.seed,
        ),
        validate=not args.no_validate,
        query_timeout_s=args.query_timeout or None,
        backpressure_depth=args.backpressure,
    )


def install_cli_fault_plan(args) -> None:
    """Parse and install --fault-plan process-wide (no-op when empty)."""
    plan = parse_fault_plan(args.fault_plan, seed=args.seed)
    if plan is not None:
        install_fault_plan(plan)
        print(f"fault plan installed: {args.fault_plan}", flush=True)


def write_diag_json(path: str, engine, rep) -> None:
    """Engine diag + StreamReport resilience counters as one JSON artifact."""
    if not path:
        return
    import dataclasses
    import json

    from repro.engine.faults import active_fault_plan

    plan = active_fault_plan()
    payload = {
        "diag": dataclasses.asdict(engine.diag),
        "report": {
            "batches": rep.batches,
            "edges": rep.edges,
            "resumed_from": rep.resumed_from,
            "retries": rep.retries,
            "quarantined_batches": rep.quarantined_batches,
            "duplicate_batches": rep.duplicate_batches,
            "degraded_queries": rep.degraded_queries,
            "max_staleness": rep.max_staleness,
            "query_fallbacks": rep.query_fallbacks,
            "dead_letter_reasons": rep.dead_letters.reasons()
            if rep.dead_letters else [],
        },
        "fault_plan": plan.summary() if plan else None,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"diag written to {path}", flush=True)


def print_resilience_summary(engine, rep) -> None:
    """One line of resilience accounting whenever anything non-trivial
    happened (silent on the happy path)."""
    d = engine.diag
    if not any((rep.retries, rep.quarantined_batches, rep.duplicate_batches,
                rep.degraded_queries, rep.query_fallbacks,
                d.ckpt_corrupt_skipped)):
        return
    print(f"resilience: retries={rep.retries} "
          f"quarantined={rep.quarantined_batches} "
          f"duplicates={rep.duplicate_batches} "
          f"degraded_queries={rep.degraded_queries} "
          f"(max_staleness={rep.max_staleness}) "
          f"query_fallbacks={rep.query_fallbacks} "
          f"ckpt_corrupt_skipped={d.ckpt_corrupt_skipped}", flush=True)


def add_scheme_flags(ap) -> None:
    ap.add_argument("--scheme", default="global",
                    help="estimator scheme: any name in repro.core.SCHEMES "
                         "(global = one triangle count per tenant; local = "
                         "per-vertex counts via vertex-partitioned pools)")
    ap.add_argument("--vertices", type=int, default=0,
                    help="local scheme: vertex-id bound for the per-vertex "
                         "output (0 = use --nodes)")
    ap.add_argument("--pools", type=int, default=1,
                    help="local scheme: estimator pools vertices hash into "
                         "(must divide --estimators)")


def format_topk(est, true_counts=None, top: int = 5) -> str:
    """``v:est`` (optionally ``(true t)``) for the top vertices — the one
    per-vertex summary format both drivers print."""
    import numpy as np

    parts = []
    for vtx in np.argsort(est)[::-1][:top]:
        s = f"{int(vtx)}:{float(est[vtx]):.1f}"
        if true_counts is not None:
            s += f"(true {int(true_counts[vtx])})"
        parts.append(s)
    return f"[{' '.join(parts)}]"


def print_local_estimates(est, tenant, true_counts=None, top: int = 5) -> None:
    """Per-vertex output: the sum/3 global cross-check plus the top vertices."""
    import numpy as np

    line = (f"local[tenant {tenant}] sum/3={float(est.sum()) / 3:.1f} "
            f"top{top}={format_topk(est, true_counts, top)}")
    if true_counts is not None:
        denom = np.maximum(true_counts.sum(), 1)
        line += f" l1.err={np.abs(est - true_counts).sum() / denom:.3%}"
    print(line, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", choices=("ba", "er", "planted"), default="ba")
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--edges", type=int, default=20000)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--triangles", type=int, default=100)
    ap.add_argument("--estimators", type=int, default=65536)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=1,
                    help="batches fused per dispatch (lax.scan superbatch); "
                         "state is bit-identical for any value")
    ap.add_argument("--groups", type=int, default=9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=1,
                    help="independent estimator banks over the same stream")
    ap.add_argument("--backend", default="auto",
                    help="auto or any name in repro.engine.backends.BACKENDS")
    add_scheme_flags(ap)
    add_dynamic_flags(ap)
    add_resilience_flags(ap)
    ap.add_argument("--assert-rel-err", type=float, default=0.0,
                    help="exit nonzero unless tenant 0's estimate lands "
                         "within this relative error of the true (live) "
                         "count — the CI smoke check")
    ap.add_argument("--mesh", default="",
                    help="device mesh spec, e.g. '8' or 'tenants=2,estimators=4' "
                         "(see repro.launch.mesh.make_stream_mesh and "
                         "docs/scaling.md)")
    ap.add_argument("--tenant-axis", default="tenants",
                    help="mesh axis carrying the bank's tenant dimension")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N CPU host devices (testing a mesh without "
                         "accelerators)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_stream_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0, help="0 = off")
    args = ap.parse_args()

    edges, tau = make_stream(args)
    dynamic = bool(args.deletions or args.window or args.decay)
    truth_edges = edges
    if dynamic:
        stream, live = make_dynamic_stream(args, edges)
        truth_edges = live
        tau = count_triangles(live) if len(live) <= 2_000_000 else None
        print(f"stream: m={len(edges)} signed={len(stream)} "
              f"live={len(live)} tau_live={tau}")
    else:
        print(f"stream: m={len(edges)} tau={tau}")

    install_cli_fault_plan(args)
    res = resilience_from_args(args)
    engine = build_engine(args)
    if args.deletions:
        # deletion batches break insert runs, so drive the signed service loop
        rep = run_signed_stream(
            engine,
            signed_batches(stream, args.batch),
            ckpt_dir=args.ckpt_dir if args.ckpt_every else None,
            ckpt_every=args.ckpt_every,
            resilience=res,
        )
    else:
        rep = run_stream(
            engine,
            batches(edges, args.batch),
            ckpt_dir=args.ckpt_dir if args.ckpt_every else None,
            ckpt_every=args.ckpt_every,
            resilience=res,
        )
    dt = max(rep.seconds, 1e-9)
    print(f"processed {rep.edges} edges in {dt:.2f}s "
          f"({rep.edges/dt/1e6:.2f}M edges/s, r={args.estimators})")
    print_resilience_summary(engine, rep)
    write_diag_json(args.diag_json, engine, rep)
    if dynamic:
        print(f"dynamic: deletes={engine.diag.delete_batches} batches "
              f"expired={engine.diag.window_expired} edges "
              f"(dyn_step={engine.dyn_step})")
    ests = engine.estimate()
    if args.scheme == "local":
        true_counts = None
        if tau is not None:
            n_vertices = args.vertices or args.nodes
            true_counts = local_triangle_counts(truth_edges, n_vertices)
        for t in range(args.tenants):
            print_local_estimates(ests[t], t, true_counts)
        return
    est = float(ests[0])
    print(f"estimate: {est:.1f}" + (
        f"  true: {tau}  rel.err: {abs(est-tau)/max(tau,1):.3%}" if tau else ""))
    for t in range(1, args.tenants):
        e = float(ests[t])
        print(f"estimate[tenant {t}]: {e:.1f}" + (
            f"  rel.err: {abs(e-tau)/max(tau,1):.3%}" if tau else ""))
    if args.assert_rel_err:
        if tau is None:
            sys.exit("--assert-rel-err needs a computable true count")
        err = abs(est - tau) / max(tau, 1)
        if err > args.assert_rel_err:
            sys.exit(f"estimate {est:.1f} misses true {tau} by {err:.3%} "
                     f"(> {args.assert_rel_err:.3%})")
        print(f"rel.err {err:.3%} within {args.assert_rel_err:.3%} OK")


if __name__ == "__main__":
    main()
