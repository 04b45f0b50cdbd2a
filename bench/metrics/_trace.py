"""Shared arithmetic of the trace-based metric readers."""
from __future__ import annotations


def idle_percent(record: dict):
    """Share of the traced window in which no op ran on the device."""
    red = record.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def ingest_ms_per_batch(record: dict):
    """Device milliseconds of the ingest programs per batch they folded in."""
    red = record.get("trace")
    role = (red or {}).get("roles", {}).get("ingest")
    per = record["counters"].get("batches_per_dispatch")
    if not role or not role["executions"] or not per:
        return None
    return 1e3 * role["device_s"] / (role["executions"] * per)


def least_ingest_bytes(r: int, s: int, k: int) -> float:
    """The least HBM traffic per batch of any ingest that keeps the state in
    HBM: read the batch (8 bytes an edge), read and write each estimator's
    21 bytes once per dispatch of ``k`` batches, and write and read once the
    sorted structure of the batch's 2s arcs at 8 bytes each."""
    return 8 * s + 2 * 2 * s * 8 + 2 * 21 * r / k
