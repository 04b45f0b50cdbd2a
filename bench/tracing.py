"""From a profiler trace of the window to the numbers the per-layer metrics
read: device busy time, device time per XLA module role, the sort ops inside
the ingest modules, the top device ops, and the longest idle gaps labelled
by the harness span the host was in.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists (``Trace``): every plane, every line and every event, named as the
profiler names them. ``reduce`` picks what it reads from those lists alone,
so the reduction is tested on a small recorded trace without a chip, and a
metric reader can reduce the same lists in its own way.

Conventions of the trace (read off a TPU v5 lite trace):

- a device is a plane named ``/device:TPU:<n>``; its line ``XLA Modules``
  holds one event per program execution, named after the jitted function
  (``jit_<name>(<fingerprint>)``), and its line ``XLA Ops`` one event per
  HLO op execution, named by the op's HLO text (``%sort.36 = ...``;
  ``op_name`` gives ``sort.36``). Ops nest: the ops of a ``while`` body lie
  inside the ``while`` op's own event, so op times are taken as self times;
- the host is the plane ``/host:CPU``; the harness's own spans
  (``jax.profiler.TraceAnnotation``, named ``bench.*``) are events on its
  thread lines, on the same clock as the device events.
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


@dataclass
class Line:
    name: str
    events: list  # [name, start_ns, duration_ns]


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Trace:
    planes: list

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls([
            Plane(p["name"], [Line(ln["name"], ln["events"]) for ln in p["lines"]])
            for p in data["planes"]
        ])


def load(trace_dir: pathlib.Path) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, whole, as plain lists."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    return Trace([
        Plane(p.name, [
            Line(ln.name, [[e.name, float(e.start_ns), float(e.duration_ns)] for e in ln.events])
            for ln in p.lines
        ])
        for p in data.planes
    ])


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = (s32[...]) fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def nest(ops):
    """Self time and top-level ancestor of each op event: ``(name, self_ns,
    top)``, where ``top`` is the outermost op containing it (itself when it
    is not nested)."""
    out, stack = [], []  # stack: [index into out, end]
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        top = out[stack[0][0]][2] if stack else name
        if stack:
            out[stack[-1][0]][1] -= e - s
        out.append([name, e - s, top])
        stack.append([len(out) - 1, e])
    return out


def union_ns(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def module_role(name: str, roles: dict) -> str:
    """The role of an XLA module name from the table (first pattern that
    matches); ``other`` when none does."""
    for role, patterns in roles.items():
        if any(re.search(p, name) for p in patterns):
            return role
    return "other"


def clip(events, lo: float, hi: float):
    """Events overlapping ``[lo, hi]``, cut to it: ``(name, start, end)``."""
    out = []
    for name, s, d in events:
        e = s + d
        if e > lo and s < hi:
            out.append((name, max(s, lo), min(e, hi)))
    return out


def reduce(trace: Trace, table: dict) -> dict | None:
    """Busy and idle time of the window on the device(s), device seconds and
    executions per module role, sort-op seconds inside each role, the top
    device ops and the longest idle gaps with the harness span around each.
    Times are averaged over the devices in the trace."""
    roles = table["roles"]
    sort_op = re.compile(table["sort_op"])
    host = [p for p in trace.planes if p.name == HOST_PLANE]
    spans = [
        ev for p in host for ln in p.lines for ev in ln.events
        if ev[0].startswith("bench.")
    ]
    windows = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    devices = [p for p in trace.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        return None  # no chip in the trace (a rehearsal): nothing to read

    busy, per_role, ops, idle = 0.0, {}, {}, []
    for dev in devices:
        lines = {ln.name: ln.events for ln in dev.lines}
        mods = clip(lines.get(MODULES_LINE, []), lo, hi)
        oplist = [(op_name(n), s, e) for n, s, e in clip(lines.get(OPS_LINE, []), lo, hi)]
        busy += union_ns([(s, e) for _, s, e in oplist] or [(s, e) for _, s, e in mods])
        mod_spans = []
        for name, s, e in mods:
            role = module_role(name, roles)
            r = per_role.setdefault(role, {"device_s": 0.0, "executions": 0, "sort_s": 0.0})
            r["device_s"] += (e - s) / 1e9
            r["executions"] += 1
            mod_spans.append((s, e, role))
        mod_spans.sort()
        j = 0
        for name, self_ns, top in sorted(nest(oplist), key=lambda x: x[0]):
            key = name if top == name else f"{top}/{name}"
            ops[key] = ops.get(key, 0.0) + self_ns / 1e9
        for name, s, e in sorted(oplist, key=lambda x: x[1]):
            if sort_op.search(name):
                while j < len(mod_spans) and mod_spans[j][1] < s:
                    j += 1
                if j < len(mod_spans) and mod_spans[j][0] <= s:
                    per_role[mod_spans[j][2]]["sort_s"] += (e - s) / 1e9
        for g0, g1 in gaps([(s, e) for _, s, e in oplist] or [(s, e) for _, s, e in mods], lo, hi):
            idle.append((g1 - g0, g0, g1))
    n = len(devices)
    for r in per_role.values():
        r["device_s"] /= n
        r["sort_s"] /= n
        r["executions"] /= n
    idle.sort(reverse=True)
    labelled = []
    for length, g0, g1 in idle[:10]:
        mid = (g0 + g1) / 2
        inner = [
            (d, name) for name, s, d in spans
            if name != WINDOW_SPAN and s <= mid <= s + d
        ]
        label = min(inner)[1] if inner else "outside harness spans"
        labelled.append([label, length / 1e9 / n])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "roles": per_role,
        "top_ops": sorted(([k, v / n] for k, v in ops.items()), key=lambda x: -x[1])[:10],
        "idle_gaps": labelled,
    }
