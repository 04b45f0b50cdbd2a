"""The harness's arithmetic: the window-closing rule and the least bytes of
the ingest roofline."""
from bench.harness import BENCH, load_module
from bench.metrics._trace import least_ingest_bytes

stream_driver = load_module(BENCH / "drivers" / "stream.py")


class FakeStream:
    def take(self, start, n):
        return start


def test_window_closes_on_the_first_report_boundary_after_the_clock():
    clock = iter([0.0, 0.0, 5.0, 10.0, 10.5, 11.0]).__next__
    got = [w for w, _ in stream_driver.source(FakeStream(), 1, 2, until=10.0, clock=clock)]
    # checked at batches 0, 2, 4, 6: 6 is the first boundary at or past 10 s
    assert got == [0, 1, 2, 3, 4, 5]


def test_source_counts_batches_from_the_stream_start():
    got = [w for w, _ in stream_driver.source(FakeStream(), 16, 4, n_batches=3)]
    assert got == [0, 16, 32]


def test_least_ingest_bytes():
    # read the batch (8 s), the arcs' sorted structure written and read (32 s),
    # the 21-byte state read and written once per dispatch of k batches
    assert least_ingest_bytes(r=2**21, s=2**20, k=1) == 40 * 2**20 + 42 * 2**21
    assert least_ingest_bytes(r=2**21, s=2**14, k=4) == 40 * 2**14 + 42 * 2**21 / 4
    floor_ms = least_ingest_bytes(2**21, 2**20, 1) / 819e9 * 1e3
    assert 0.15 < floor_ms < 0.17
