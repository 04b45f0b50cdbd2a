"""Device time of the ingest programs (``bench/modules.json`` role
``ingest``) per batch, from the profiler trace."""
from bench.metrics._trace import ingest_ms_per_batch


def read(record: dict):
    return ingest_ms_per_batch(record)
