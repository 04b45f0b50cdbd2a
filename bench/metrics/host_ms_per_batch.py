"""Host milliseconds per batch that the stream loop spends in the program's
own work (``repro.stream.*`` and ``repro.engine.*`` spans on the loop's
thread, less the ``repro.engine.wait`` blocks on the device) inside the
traced window. In a closed loop the device waits for this time."""
from bench.metrics._spans import loop_busy_ns


def read(record: dict):
    batches = record["counters"].get("batches")
    if record.get("trace") is None or not batches:
        return None  # no chip in the trace (a rehearsal), or nothing ingested
    ns = loop_busy_ns(record["raw_trace"])
    return None if ns is None else ns / 1e6 / batches
