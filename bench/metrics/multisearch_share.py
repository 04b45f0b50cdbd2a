"""Share of the ingest programs' device time (``bench/modules.json`` role
``ingest``) in ops under the program's ``multisearch`` scope: the Q1, Q2 and
closing-edge searches, by the scope path the profiler records with each op
(``bench/metrics/_scopes.py``)."""
from bench import harness
from bench.metrics import _scopes


def read(record: dict):
    table = _scopes.scopes(harness.CACHE / "trace" / record["cell"])
    if table is None:
        return None
    roles = harness.load_json(harness.BENCH / "modules.json")["roles"]
    return _scopes.share(record["raw_trace"], table, roles, "multisearch")
