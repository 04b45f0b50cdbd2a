"""Share of the traced window in which the device ran no operation
(profiler trace: 1 - union of the op intervals over the window)."""
from bench.metrics._trace import idle_percent


def read(record: dict):
    return idle_percent(record)
