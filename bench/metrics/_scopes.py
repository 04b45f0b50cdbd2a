"""The program's named scopes on the device's ops, and the device time under
each.

The program wraps each stage of its ingest and estimate programs in
``jax.named_scope``: ``step1``, ``rng``, ``rank_all``, ``q1``, ``q2``,
``closing``, ``delete``, ``estimate``, and ``multisearch`` inside every
search. XLA keeps the scope path in each instruction's ``op_name``, and the
TPU profiler writes it as the ``tf_op`` stat of the op's event metadata on
the device plane (``jit(bulk_update)/vmap(q2)/multisearch/jit(searchsorted)/
vmap()/while:``). ``jax.profiler.ProfileData`` gives the events' own stats
but not their metadata's, so ``scopes`` reads the ``.xplane.pb`` itself:
a few protobuf fields of ``XSpace`` (tsl/profiler/protobuf/xplane.proto).

Op times are self times (``tracing.nest``); an op belongs to the XLA module
execution that contains it, and the module's role comes from
``bench/modules.json`` (``tracing.module_role``), as in ``tracing.reduce``.
"""
from __future__ import annotations

import pathlib
import re

from bench import tracing
from bench.metrics._spans import window

STAGES = ("step1", "rng", "rank_all", "q1", "q2", "closing", "delete", "estimate")
_WRAP = re.compile(r"^(\w+\()+|\)+$")
_PROGRAM = re.compile(r"\((\d+)\)$")


# -- protobuf wire format, as much as XSpace needs --------------------------
def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of one message: ints for varints, slices
    for length-delimited fields, fixed-width fields skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            ln, i = _varint(b, i)
            yield num, b[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_values(entries):
    for entry in entries:
        for num, v in _fields(entry):
            if num == 2:
                yield v


def _plane_scopes(plane) -> tuple[str, dict]:
    """A plane's name and ``{program id: {op name: tf_op}}`` of its event
    metadata."""
    name, events, stat_names = "", [], {}
    for num, v in _fields(plane):
        if num == 2:
            name = bytes(v).decode()
        elif num == 4:
            events.append(v)
        elif num == 5:
            for meta in _map_values([v]):
                f = dict(_fields(meta))
                stat_names[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
    if not tracing.DEVICE_PLANE.match(name):
        return name, {}
    out: dict = {}
    for meta in _map_values(events):
        op, stats = None, {}
        for num, v in _fields(meta):
            if num == 2:
                op = bytes(v).decode()
            elif num == 5:
                f = dict(_fields(v))
                key = stat_names.get(f.get(1))
                if 5 in f:
                    stats[key] = bytes(f[5]).decode()
                elif 7 in f:  # a string kept once, as a stat name
                    stats[key] = stat_names.get(f[7])
                elif 3 in f or 4 in f:
                    stats[key] = f.get(3, f.get(4))
        if op and stats.get("tf_op") is not None and "program_id" in stats:
            out.setdefault(str(stats["program_id"]), {})[tracing.op_name(op)] = stats["tf_op"]
    return name, out


def scopes(trace_dir: pathlib.Path):
    """``{device plane: {program id: {op name: scope path}}}`` from the
    newest ``.xplane.pb`` under ``trace_dir``; ``None`` when there is none or
    no device plane holds a scope (a rehearsal on the CPU)."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    data = memoryview(files[-1].read_bytes())
    out = {}
    for num, plane in _fields(data):
        if num == 1:
            name, table = _plane_scopes(plane)
            if table:
                out[name] = table
    return out or None


# -- device time by scope ----------------------------------------------------
def parts(path: str) -> list[str]:
    """``jit(bulk_update)/vmap(q1)/multisearch/...:`` -> ``["bulk_update",
    "q1", "multisearch", ...]``: the scopes, without JAX's transform
    wrappers and the trailing op type."""
    return [_WRAP.sub("", p) for p in path.rsplit(":", 1)[0].split("/")]


def stage(path) -> str:
    """The innermost stage scope of a path, ``other`` for a scoped op in no
    stage and ``unscoped`` for an op without a path."""
    if path is None:
        return "unscoped"
    names = [p for p in parts(path) if p in STAGES]
    return names[-1] if names else "other"


def role_ops(trace, table: dict, roles: dict, role: str = "ingest"):
    """Device seconds of the window's ``role`` modules, and ``(op name, self
    seconds, scope path or None)`` of every op inside them, averaged over the
    device planes that ``table`` (from ``scopes``) covers; ``None`` when it
    covers none."""
    lo, hi = window(trace)
    total, ops, n = 0.0, [], 0
    for dev in trace.planes:
        if dev.name not in table:
            continue
        n += 1
        lines = {ln.name: ln.events for ln in dev.lines}
        mods = sorted(
            (s, e, _PROGRAM.search(name))
            for name, s, e in tracing.clip(lines.get(tracing.MODULES_LINE, []), lo, hi)
            if tracing.module_role(name, roles) == role
        )
        total += sum(e - s for s, e, _ in mods)
        oplist = sorted(
            ((tracing.op_name(name), s, e)
             for name, s, e in tracing.clip(lines.get(tracing.OPS_LINE, []), lo, hi)),
            key=lambda x: (x[1], -x[2]),
        )
        j = 0
        for (name, s, _e), (_, self_ns, _top) in zip(oplist, tracing.nest(oplist)):
            while j < len(mods) and mods[j][1] < s:
                j += 1
            if j < len(mods) and mods[j][0] <= s:
                program = mods[j][2].group(1) if mods[j][2] else ""
                path = table[dev.name].get(program, {}).get(name)
                ops.append((name, self_ns, path))
    if not n:
        return None
    return total / 1e9 / n, [(name, ns / 1e9 / n, path) for name, ns, path in ops]


def share(trace, table: dict, roles: dict, scope: str, role: str = "ingest"):
    """Percent of the ``role`` modules' device time in ops whose scope path
    holds ``scope``; ``None`` when no op does (a program without the
    scope)."""
    got = role_ops(trace, table, roles, role)
    if got is None:
        return None
    device_s, ops = got
    inside = [sec for _, sec, path in ops if path and scope in parts(path)]
    if device_s <= 0 or not inside:
        return None
    return 100.0 * sum(inside) / device_s


def split(trace, table: dict, roles: dict, role: str = "ingest") -> dict | None:
    """Seconds of the ``role`` modules' device time by ``stage``, with
    ``between ops`` for module time in no op."""
    got = role_ops(trace, table, roles, role)
    if got is None:
        return None
    device_s, ops = got
    out: dict = {}
    for _, sec, path in ops:
        out[stage(path)] = out.get(stage(path), 0.0) + sec
    out["between ops"] = device_s - sum(out.values())
    return out
