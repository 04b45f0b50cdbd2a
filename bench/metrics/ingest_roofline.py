"""The ingest programs' share of their HBM roofline: the least bytes a batch
needs (``least_ingest_bytes``) at the chip's peak bandwidth, over the
measured device time per batch. Bound by bytes: the ingest does integer
sorts, searches and gathers, and no floating-point work worth counting."""
from bench.metrics._trace import ingest_ms_per_batch, least_ingest_bytes


def read(record: dict):
    ms = ingest_ms_per_batch(record)
    if not ms or not record.get("peaks"):
        return None
    c = record["counters"]
    floor_s = least_ingest_bytes(c["r"], c["batch"], c["batches_per_dispatch"]) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
