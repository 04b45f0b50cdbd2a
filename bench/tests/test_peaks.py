"""The table of peaks: a device kind that is not in it is an error."""
import pytest

from bench import harness


def test_known_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["bf16_flop_per_s"] == 197e12 and p["int8_op_per_s"] == 393e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(harness.BenchError):
        harness.peaks(kind)
