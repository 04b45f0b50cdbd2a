"""The readers of the program's own spans and scopes: ``multisearch_share``,
``host_ms_per_batch`` and ``_spans.label_gaps``, on hand-built traces and on
a trace recorded on a TPU v5 lite."""
import gzip
import json

import pytest

from bench import harness, tracing
from bench.harness import BENCH, load_json, load_module
from bench.metrics import _scopes, _spans

TABLE = load_json(BENCH / "modules.json")
MS = 1e6  # nanoseconds per millisecond
DEV = "/device:TPU:0"
SEARCH = "jit(bulk_update)/vmap(q1)/multisearch/jit(searchsorted)/while:"


def synthetic(program_spans: bool = True):
    """A 100 ms window, two ingest executions of 30 ms with their ops, and
    the stream loop's spans round them (and a producer thread's)."""
    loop = [
        ["bench.window", 0.0, 100 * MS],
        ["bench.run_stream", 0.0, 95 * MS],
    ]
    if program_spans:
        loop += [
            ["repro.stream.fetch", 1 * MS, 4 * MS],  # 1-5
            ["repro.stream.validate", 5 * MS, 2 * MS],  # 5-7
            ["repro.engine.stage", 7 * MS, 2 * MS],  # 7-9
            ["repro.engine.dispatch", 9 * MS, 1 * MS],  # 9-10
            ["repro.stream.report", 10 * MS, 32 * MS],  # 10-42
            ["repro.engine.estimate", 10.5 * MS, 31.5 * MS],
            ["repro.engine.wait", 11 * MS, 30 * MS],  # 11-41
            ["repro.stream.fetch", 42 * MS, 3 * MS],  # 42-45
            ["repro.engine.dispatch", 45 * MS, 1 * MS],  # 45-46
            ["repro.engine.wait", 50 * MS, 30 * MS],  # 50-80
        ]
    producer = [["repro.prefetch.produce", 0.0, 60 * MS]] if program_spans else []
    host = tracing.Plane(tracing.HOST_PLANE, [
        tracing.Line("python3", producer), tracing.Line("python3", loop),
    ])
    dev = tracing.Plane(DEV, [
        tracing.Line("XLA Modules", [
            ["jit_bulk_update(7)", 10 * MS, 30 * MS],
            ["jit_bulk_update(7)", 46 * MS, 30 * MS],
        ]),
        tracing.Line("XLA Ops", [
            ["while.1", 10 * MS, 20 * MS],
            ["fusion.2", 12 * MS, 10 * MS],  # inside the while: 10 ms of its 20
            ["sort.3", 30 * MS, 10 * MS],
            ["while.1", 46 * MS, 20 * MS],
            ["fusion.2", 48 * MS, 10 * MS],
            ["sort.3", 66 * MS, 10 * MS],
        ]),
    ])
    return tracing.Trace([host, dev])


SCOPES = {DEV: {"7": {
    "while.1": SEARCH,
    "fusion.2": "jit(bulk_update)/vmap(q1)/multisearch/jit(searchsorted)/while/body/lt:",
    "sort.3": "jit(bulk_update)/vmap(rank_all)/jit(argsort)/sort:",
}}}


def test_scope_paths():
    assert _scopes.parts(SEARCH) == [
        "bulk_update", "q1", "multisearch", "searchsorted", "while"]
    assert _scopes.stage(SEARCH) == "q1"
    assert _scopes.stage("jit(chunk_update)/vmap(step1)/rng/jit(_randint)/add:") == "rng"
    assert _scopes.stage("jit(chunk_update)/vmap()/transpose:") == "other"
    assert _scopes.stage(None) == "unscoped"


def test_multisearch_share_and_split():
    t = synthetic()
    roles = TABLE["roles"]
    # 2 x 20 ms of the while (its self time and its body's) of 60 ms
    assert _scopes.share(t, SCOPES, roles, "multisearch") == pytest.approx(100 * 40 / 60)
    split = _scopes.split(t, SCOPES, roles)
    assert split["q1"] == pytest.approx(0.04) and split["rank_all"] == pytest.approx(0.02)
    assert split["between ops"] == pytest.approx(0.0)
    # a program without the scope, or a trace without scopes, reads nothing
    unscoped = {DEV: {"7": {"while.1": "jit(bulk_update)/vmap()/jit(searchsorted)/while:"}}}
    assert _scopes.share(t, unscoped, roles, "multisearch") is None
    assert _scopes.share(t, {}, roles, "multisearch") is None


def test_host_ms_per_batch():
    t = synthetic()
    record = {"raw_trace": t, "trace": tracing.reduce(t, TABLE), "counters": {"batches": 2}}
    read = load_module(BENCH / "metrics" / "host_ms_per_batch.py").read
    # 1-11 and 41-46 in loop spans outside a wait: 15 ms over 2 batches;
    # the producer's thread and the harness's spans do not count
    assert read(record) == pytest.approx(7.5)
    assert read({**record, "raw_trace": synthetic(program_spans=False)}) is None
    assert read({**record, "trace": None}) is None  # a rehearsal: no chip


def test_label_gaps():
    gaps = _spans.label_gaps(synthetic())
    # idle: 0-10, 40-46 and 76-100 ms
    assert [round(g["seconds"] * 1e3, 6) for g in gaps] == [24.0, 10.0, 6.0]
    first = next(g for g in gaps if g["start_s"] == 0)
    assert first["parts"] == pytest.approx({
        "unspanned": 0.001, "repro.stream.fetch": 0.004, "repro.stream.validate": 0.002,
        "repro.engine.stage": 0.002, "repro.engine.dispatch": 0.001})
    assert first["label"] == "repro.stream.fetch"
    mid = next(g for g in gaps if g["start_s"] == pytest.approx(0.04))
    assert mid["parts"] == pytest.approx({
        "repro.engine.wait": 0.001, "repro.engine.estimate": 0.001,
        "repro.stream.fetch": 0.003, "repro.engine.dispatch": 0.001})
    last = gaps[0]
    assert last["parts"] == pytest.approx({"repro.engine.wait": 0.004, "unspanned": 0.02})
    assert last["label"] == "unspanned"
    # the parent program: every gap unspanned
    assert {g["label"] for g in _spans.label_gaps(synthetic(program_spans=False))} == {"unspanned"}


def _encode(fields) -> bytes:
    """Protobuf wire bytes of ``[(field number, int | bytes | list)]``."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, list):
            v = _encode(v)
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_scopes_read_the_op_metadata(tmp_path):
    # XSpace.planes[] with the device plane's event_metadata and
    # stat_metadata maps (tsl/profiler/protobuf/xplane.proto)
    stat_meta = [(5, [(1, 1), (2, [(1, 1), (2, b"tf_op")])]),
                 (5, [(1, 2), (2, [(1, 2), (2, b"program_id")])]),
                 (5, [(1, 3), (2, [(1, 3), (2, SEARCH.encode())])])]
    ops = [
        (4, [(1, 10), (2, [(1, 10), (2, b"%while.1 = s32[8] while(...)"),
                           (5, [(1, 1), (5, SEARCH.encode())]), (5, [(1, 2), (3, 7)])])]),
        (4, [(1, 11), (2, [(1, 11), (2, b"%sort.3 = s64[8] sort(...)"),
                           (5, [(1, 1), (7, 3)]), (5, [(1, 2), (3, 7)])])]),
        (4, [(1, 12), (2, [(1, 12), (2, b"%copy-done.4 = s32[8] copy-done(...)"),
                           (5, [(1, 2), (3, 7)])])]),
    ]
    space = _encode([(1, [(2, DEV.encode()), *ops, *stat_meta]),
                     (1, [(2, tracing.HOST_PLANE.encode()), *ops])])
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "x.xplane.pb").write_bytes(space)
    assert _scopes.scopes(tmp_path) == {DEV: {"7": {"while.1": SEARCH, "sort.3": SEARCH}}}
    assert _scopes.scopes(tmp_path / "empty") is None


def recorded():
    """A trace recorded on one TPU v5 lite: the window of a rehearsal-sized
    ``paper_r2m.bulk_1m`` run (``--rehearse --trace 1``, 5 batches): the
    harness's and the program's host spans, the device's module and op lines,
    and each op's ``tf_op`` scope from the xplane's event metadata."""
    with gzip.open(BENCH / "tests" / "data" / "tpu_v5e_spans_trace.json.gz", "rt") as f:
        data = json.load(f)
    return tracing.Trace.from_json(data), data["scopes"]


def test_recorded_scopes():
    t, scopes = recorded()
    share = _scopes.share(t, scopes, TABLE["roles"], "multisearch")
    assert 90 < share < 99
    split = _scopes.split(t, scopes, TABLE["roles"])
    ingest = tracing.reduce(t, TABLE)["roles"]["ingest"]["device_s"]
    assert sum(split.values()) == pytest.approx(ingest)
    assert max(split, key=split.get) == "q1"
    assert split["unscoped"] < 0.01 * ingest


def test_recorded_readers(monkeypatch):
    t, scopes = recorded()
    record = {"cell": "paper_r2m.bulk_1m", "raw_trace": t,
              "trace": tracing.reduce(t, TABLE), "counters": {"batches": 5}}
    monkeypatch.setattr(_scopes, "scopes", lambda _dir: scopes)
    read = lambda name: load_module(BENCH / "metrics" / f"{name}.py").read(record)
    assert read("multisearch_share") == pytest.approx(
        _scopes.share(t, scopes, TABLE["roles"], "multisearch"))
    ms = read("host_ms_per_batch")
    assert 0 < ms < 1e3 * record["trace"]["window_s"] / 5


def test_recorded_gaps():
    t, _ = recorded()
    gaps = _spans.label_gaps(t)
    red = tracing.reduce(t, TABLE)
    idle = sum(g["seconds"] for g in gaps)
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert sum(sum(g["parts"].values()) for g in gaps) == pytest.approx(idle)
    assert {g["label"] for g in gaps} <= {
        "unspanned", *(n for n, _, _ in _spans.loop_spans(t))}
    # the window opens on the skipped prefix and the first batch's staging,
    # inside the program's spans
    assert next(g for g in gaps if g["start_s"] == 0)["label"].startswith("repro.")
    unspanned = sum(g["parts"].get("unspanned", 0.0) for g in gaps)
    assert unspanned < 0.1 * idle


def test_cells_report_the_new_metrics():
    bench = load_json(harness.ROOT / "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    for name in ("multisearch_share", "host_ms_per_batch"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == cells and metric["moves"] == "edge_rate"
        assert (BENCH / "metrics" / f"{name}.py").exists()
