"""PR 8 tentpole bench: fused chunk-ingest pipeline vs the reference scan.

Rows land in BENCH_streaming.json's main ``results`` grid labeled by
``pipeline`` ("scan" = per-batch reference loop under lax.scan, "fused" =
the hoisted-RNG single-program pipeline in repro.core.bulk; on TPU the
fused pipeline additionally runs the resident kernels). The headline claim
this grid carries (ISSUE PR 8 acceptance): with the fused pipeline the
r-degradation flattens — at batch 16384 the r=65536 rate is within 4x of
the r=512 rate, vs ~15-60x for the scan pipeline at the committed batch
sizes. The mechanism: the per-chunk cost splits into an s-linear structure
build (shared by all r) plus an r-linear query/update part; fusing trims
the r-linear part (5 of 12 search sides proven redundant, RNG hoisted out
of the scan) and large batches amortize what remains.

  PYTHONPATH=src python -m benchmarks.fused --json BENCH_streaming.json
  PYTHONPATH=src python -m benchmarks.fused --roofline roofline_fused.json
  PYTHONPATH=src python -m benchmarks.fused --smoke --json ... --roofline ...

The ``--roofline`` report quantifies bytes-touched before/after via XLA
cost_analysis on the lowered chunk programs (plus the analytic per-chunk
state-traffic model for the resident kernel, which interpret-mode
cost_analysis cannot see). Caveat inherited from repro.roofline.flops: XLA
counts a scan body ONCE, not trip-count times — both pipelines scan over
the K batches, so the comparison is per-batch-body against per-batch-body,
and the analytic table carries the xK totals.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bulk, init_state
from repro.data.graph_stream import barabasi_albert_stream, batches
from repro.primitives.ingest import set_ingest_backend

# the fused pipeline's hardware backend: resident kernels on TPU, the
# hoisted single-program XLA path elsewhere (bit-identical either way)
FUSED_BACKEND = "pallas" if jax.default_backend() == "tpu" else "xla"


def _stage_chunks(edges: np.ndarray, bs: int, chunk: int):
    its = [(jnp.asarray(W), jnp.int32(nv)) for W, nv in batches(edges, bs)]
    n_full = (len(its) // chunk) * chunk
    chunks = [
        (
            jnp.stack([its[i + j][0] for j in range(chunk)]),
            jnp.stack([its[i + j][1] for j in range(chunk)]),
        )
        for i in range(0, n_full, chunk)
    ]
    jax.block_until_ready([c[0] for c in chunks])
    return chunks, n_full * bs


def measure(
    r: int, bs: int, chunk: int, pipeline: str, edges: np.ndarray,
    smoke: bool = False,
) -> dict:
    """One (r, batch, chunk, pipeline) row. Timed region = the full-chunk
    stream only (no ragged tail), so scan and fused rows at the same
    coordinates time literally the same edges through the same chunk API —
    only the ingest-backend dispatch differs."""
    set_ingest_backend("scan" if pipeline == "scan" else FUSED_BACKEND)
    try:
        chunks, m = _stage_chunks(edges, bs, chunk)
        key = jax.random.PRNGKey(0)

        def run():
            state = init_state(r)
            for ci, (Ws, nvs) in enumerate(chunks):
                state = bulk.bulk_update_chunk_jit(state, Ws, nvs, key, ci * chunk)
            return state

        jax.block_until_ready(run().chi)  # warm
        t0 = time.perf_counter()
        jax.block_until_ready(run().chi)
        dt = time.perf_counter() - t0
    finally:
        set_ingest_backend("auto")
    return {
        "scheme": "global",
        "r": r,
        "batch": bs,
        "chunk": chunk,
        "pipeline": pipeline,
        "ingest_backend": "scan" if pipeline == "scan" else FUSED_BACKEND,
        "edges": m,
        "batches": len(chunks) * chunk,
        "smoke": smoke,
        "seconds": round(dt, 6),
        "us_per_batch": round(dt / (len(chunks) * chunk) * 1e6, 1),
        "edges_per_s": round(m / dt, 1),
    }


def bench_grid(
    *,
    r_values=(512, 4096, 65536),
    batch_sizes=(4096, 16384),
    chunk: int = 8,
    nodes: int = 80_000,
    degree: int = 8,
    pipelines=("scan", "fused"),
    smoke: bool = False,
) -> list[dict]:
    if smoke:
        r_values, batch_sizes, nodes = (2048,), (1024,), 4000
    edges = barabasi_albert_stream(nodes, degree, seed=0)
    rows = []
    for bs in batch_sizes:
        for r in r_values:
            per_pipeline = {}
            for pipeline in pipelines:
                row = measure(r, bs, chunk, pipeline, edges, smoke=smoke)
                per_pipeline[pipeline] = row["edges_per_s"]
                if "scan" in per_pipeline:
                    row["speedup_vs_scan"] = round(
                        row["edges_per_s"] / per_pipeline["scan"], 2
                    )
                rows.append(row)
                print(
                    f"# r={r} batch={bs} chunk={chunk} {pipeline}: "
                    f"{row['edges_per_s']:,.0f} edges/s",
                    flush=True,
                )
        # the acceptance ratio, per batch size: r-degradation of each pipeline
        for pipeline in pipelines:
            sub = {
                row["r"]: row["edges_per_s"]
                for row in rows
                if row["batch"] == bs and row["pipeline"] == pipeline
            }
            if len(sub) > 1:
                ratio = max(sub.values()) / min(sub.values())
                print(
                    f"# batch={bs} {pipeline}: r-degradation "
                    f"{ratio:.1f}x across r={sorted(sub)}",
                    flush=True,
                )
    return rows


# ---------------------------------------------------------------------------
# roofline: bytes touched per chunk, before/after
# ---------------------------------------------------------------------------
def _chunk_cost(fn, r: int, s: int, K: int) -> dict:
    """XLA cost_analysis of one lowered chunk program (flops, bytes)."""
    state = init_state(r)
    Ws = jnp.zeros((K, s, 2), jnp.int32)
    nv = jnp.full((K,), s, jnp.int32)
    key = jax.random.PRNGKey(0)
    compiled = jax.jit(fn).lower(state, Ws, nv, key).compile()
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def state_bytes(r: int) -> int:
    """Estimator-state footprint: f1 (r,2) i32 + chi (r,) i32 + f2 (r,2) i32
    + has_f3 (r,) bool."""
    return r * (8 + 4 + 8 + 1)


def structure_bytes(s: int) -> int:
    """One batch's RankStructure: key_desc/key_rank (2s,) i64, src/dst/pos/
    rank (2s,) i32, ekey (s,) i64, epos (s,) i32."""
    return 2 * s * (8 + 8 + 4 + 4 + 4 + 4) + s * (8 + 4)


def roofline_report(r: int = 65536, s: int = 4096, K: int = 8) -> dict:
    """Bytes-touched before/after for one (r, s, K) chunk.

    * ``cost_analysis``: XLA's numbers for the lowered scan vs fused chunk
      programs (scan-body-once caveat applies to both).
    * ``analytic_state_traffic``: the resident-kernel story cost_analysis
      cannot see — the scan pipeline moves the full estimator state through
      memory once per BATCH (read + write per scan step), the resident
      kernel moves each state tile through HBM once per CHUNK; per-batch
      structures stream past the tiles in both.
    """
    scan_cost = _chunk_cost(
        lambda st, W, n, k: bulk._bulk_update_chunk_scan(st, W, n, k, 0),
        r, s, K,
    )
    fused_cost = _chunk_cost(
        lambda st, W, n, k: bulk._bulk_update_chunk_fused(
            st, W, n, k, 0, use_kernels=False
        ),
        r, s, K,
    )
    sb, rb = state_bytes(r), structure_bytes(s)
    analytic = {
        "state_bytes": sb,
        "structure_bytes_per_batch": rb,
        # read + write the state once per batch vs once per chunk
        "scan_state_traffic_per_chunk": 2 * sb * K,
        "resident_state_traffic_per_chunk": 2 * sb,
        "structure_traffic_per_chunk": rb * K,
        "state_traffic_reduction_x": float(K),
    }
    return {
        "r": r, "s": s, "K": K,
        "cost_analysis": {"scan": scan_cost, "fused": fused_cost},
        "analytic_state_traffic": analytic,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", default=None, help="merge rows into this record")
    p.add_argument("--roofline", default=None, help="write bytes report here")
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()

    rows = bench_grid(smoke=args.smoke)
    if args.json:
        from benchmarks.run import _row_key

        with open(args.json) as f:
            payload = json.load(f)
        from benchmarks.common import merge_rows

        payload["results"] = merge_rows(payload.get("results", []), rows, _row_key)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# merged {len(rows)} fused-pipeline rows into {args.json}")
    if args.roofline:
        rep = roofline_report(
            *( (2048, 512, 4) if args.smoke else (65536, 4096, 8) )
        )
        with open(args.roofline, "w") as f:
            json.dump(rep, f, indent=2)
            f.write("\n")
        ca = rep["cost_analysis"]
        print(
            f"# roofline bytes/chunk (r={rep['r']}, s={rep['s']}, K={rep['K']}): "
            f"scan={ca['scan']['bytes_accessed']:.3e} "
            f"fused={ca['fused']['bytes_accessed']:.3e}"
        )


if __name__ == "__main__":
    main()
