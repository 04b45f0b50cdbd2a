"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only accuracy,throughput,...]
  PYTHONPATH=src python -m benchmarks.run --json BENCH_streaming.json [--smoke]

Prints ``name,us_per_call,derived`` CSV rows (plus a header). Scaled to finish
on a single CPU core; the dry-run + roofline (EXPERIMENTS.md) carry the
at-scale numbers.

``--json PATH`` runs the streaming grids instead — edges/s per
(scheme, r, batch, chunk) configuration (chunk=1 being the per-batch
baseline) plus the engine-bank (scheme, tenants x backend) streams/s grid —
and **merges** into an existing record keyed by those row coordinates, so a
rerun of one scheme's grid never clobbers another scheme's committed rows;
``--smoke`` shrinks both grids to CI scale.
``python -m benchmarks.multistream --mesh ...`` re-merges the bank grid with
tenant-sharded plans included, and ``python -m benchmarks.query_serve
--mesh ... --json ...`` merges the queries/s-under-ingest serving grid under
its own ``query_serve`` key (device-resident vs gather-to-host query paths)
without touching the ingest rows.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

from benchmarks.common import merge_rows  # write_json merges the two grids it owns


def _row_key(row: dict) -> tuple:
    """Identity of a throughput-grid row: rows missing the scheme field (the
    pre-scheme-layer format) are ``global``. ``smoke`` participates so a CI
    smoke run never replaces committed full-scale rows that happen to share
    a configuration. ``pipeline`` distinguishes the chunk-ingest dispatch
    ("scan" reference loop vs the PR 8 "fused" path, benchmarks/fused.py);
    rows that predate the field are the scan pipeline."""
    return (
        row.get("scheme", "global"),
        row["r"],
        row["batch"],
        row["chunk"],
        row.get("pipeline", "scan"),
        bool(row.get("smoke", False)),
    )


def write_json(path: str, smoke: bool) -> None:
    import jax

    from benchmarks import multistream, throughput

    old: dict = {}
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
    results = throughput.bench_grid(smoke=smoke)
    ms_rows = multistream.bench_grid(smoke=smoke)
    payload = {
        # every top-level key this writer does not own (e.g. the
        # `query_serve` serving grid) is carried over verbatim — the
        # never-clobber contract covers whole sections, not just rows
        **old,
        "schema": "repro/streaming-throughput/v1",
        "smoke": smoke,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        # merge keyed by (scheme, r, batch, chunk): landing the `local` grid
        # must not clobber the committed `global` rows (and vice versa)
        "results": merge_rows(old.get("results", []), results, _row_key),
        # the engine-bank grid (scheme, tenants x backend -> streams/s);
        # sharded-plan rows appear when the run has a mesh (python -m
        # benchmarks.multistream --host-devices N --mesh ... merges them
        # into the same file)
        "multistream": multistream.grid_section(
            merge_rows(
                old.get("multistream", {}).get("results", []),
                ms_rows,
                multistream.row_key,
            ),
            smoke,
        ),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    best = max(
        (r for r in results if r["chunk"] > 1),
        key=lambda r: r.get("speedup_vs_per_batch") or 0.0,
        default=None,
    )
    if best:
        print(
            f"# wrote {path}; best chunked speedup "
            f"{best['speedup_vs_per_batch']}x at scheme={best['scheme']} "
            f"r={best['r']} batch={best['batch']} chunk={best['chunk']}",
            file=sys.stderr,
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of bench names")
    ap.add_argument("--json", default="",
                    help="write the streaming edges/s grid to this path "
                         "(skips the CSV benches)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI smoke runs")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    if args.json:
        write_json(args.json, args.smoke)
        return

    from benchmarks import (
        accuracy,
        kernels,
        multistream,
        query_serve,
        recovery,
        schemes,
        throughput,
    )

    benches = {
        "accuracy": accuracy.main,      # paper Table 2
        "throughput": throughput.main,  # paper Figure 6
        "schemes": schemes.main,        # paper Table 3 / Section 1
        "kernels": kernels.main,        # kernel contracts + bytes
        "multistream": multistream.main,  # engine multi-tenant bank
        "query_serve": query_serve.main,  # queries/s under concurrent ingest
        "recovery": recovery.main,      # restore time + degraded queries/s
    }
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # pragma: no cover
            print(f"{name},0,ERROR={type(e).__name__}:{e}", file=sys.stderr)
            raise
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
