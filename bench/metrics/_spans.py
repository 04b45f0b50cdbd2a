"""The program's own host spans in the loaded trace.

The program names its host spans ``repro.<layer>.<what>``
(``jax.profiler.TraceAnnotation``): the stream loop's ``repro.stream.*``
(fetch, validate, report, checkpoint), the engine's ``repro.engine.*``
(stage, dispatch, estimate, and ``wait`` round every host block on device
results) and the prefetch thread's ``repro.prefetch.produce``. They are
events on the host plane's thread lines, on the device's clock. The loop's
thread is the line that holds the ``repro.engine.dispatch`` spans. A trace of
a program without these spans reads ``None`` here, never zero.
"""
from __future__ import annotations

from bench import tracing

LOOP = ("repro.stream.", "repro.engine.")
WAIT = "repro.engine.wait"
DISPATCH = "repro.engine.dispatch"
UNSPANNED = "unspanned"


def window(trace) -> tuple[float, float]:
    """``(start, end)`` ns of the harness's ``bench.window`` span."""
    for p in trace.planes:
        if p.name == tracing.HOST_PLANE:
            for ln in p.lines:
                for name, s, d in ln.events:
                    if name == tracing.WINDOW_SPAN:
                        return s, s + d
    raise ValueError("the trace holds no bench.window span")


def loop_spans(trace):
    """The ``repro.*`` spans of the stream loop's thread as ``(name, start,
    duration)``, or ``None`` when the trace holds no dispatch span."""
    best, most = None, 0
    for p in trace.planes:
        if p.name != tracing.HOST_PLANE:
            continue
        for ln in p.lines:
            n = sum(1 for ev in ln.events if ev[0] == DISPATCH)
            if n > most:
                best, most = ln, n
    if best is None:
        return None
    return [ev for ev in best.events if ev[0].startswith("repro.")]


def loop_busy_ns(trace):
    """Nanoseconds of the window in which the loop's thread is inside a
    ``repro.stream.*`` or ``repro.engine.*`` span and not inside a
    ``repro.engine.wait``: in a closed loop, the host work the device waits
    for."""
    spans = loop_spans(trace)
    if spans is None:
        return None
    lo, hi = window(trace)
    clipped = tracing.clip(spans, lo, hi)
    work = [(s, e) for n, s, e in clipped if n.startswith(LOOP) and n != WAIT]
    wait = [(s, e) for n, s, e in clipped if n == WAIT]
    return tracing.union_ns(work + wait) - tracing.union_ns(wait)


def _innermost(spans, g0: float, g1: float) -> dict:
    """Seconds of ``[g0, g1]`` under each innermost span (the latest-started
    of those covering a point; spans of one thread nest), or under none."""
    inside = [(s, s + d, n) for n, s, d in spans if s < g1 and s + d > g0]
    cuts = sorted({g0, g1, *(t for s, e, _ in inside for t in (s, e) if g0 < t < g1)})
    parts: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(s, -e, n) for s, e, n in inside if s <= mid < e]
        label = max(cover)[2] if cover else UNSPANNED
        parts[label] = parts.get(label, 0.0) + (b - a) / 1e9
    return parts


def label_gaps(trace) -> list[dict]:
    """Every stretch of the window in which a device ran no op, longest
    first, named by the innermost ``repro.*`` span the loop's thread was in:
    ``{"device", "start_s"`` (from the window's start) ``, "seconds",
    "label"`` (the span that covers most of the gap, or ``unspanned``)
    ``, "parts"`` (seconds under each innermost span)``}``."""
    lo, hi = window(trace)
    spans = loop_spans(trace) or []
    out = []
    for dev in trace.planes:
        if not tracing.DEVICE_PLANE.match(dev.name):
            continue
        lines = {ln.name: ln.events for ln in dev.lines}
        busy = tracing.clip(lines.get(tracing.OPS_LINE) or lines.get(tracing.MODULES_LINE, []), lo, hi)
        for g0, g1 in tracing.gaps([(s, e) for _, s, e in busy], lo, hi):
            parts = _innermost(spans, g0, g1)
            out.append({
                "device": dev.name, "start_s": (g0 - lo) / 1e9,
                "seconds": (g1 - g0) / 1e9,
                "label": max(parts.items(), key=lambda kv: kv[1])[0],
                "parts": parts,
            })
    return sorted(out, key=lambda g: -g["seconds"])
