#!/usr/bin/env python3
"""Split a profiler trace's device time by the operator that spent it.

    python3 benchmark/trace_scopes.py <trace.xplane.pb | trace dir> \
        [--passes N]

The program runs each query as one XLA program and traces each
operator under a scope from ``cylon_tpu.utils.tracing.SCOPES``. Every
device operation's metadata then carries the JAX name stack it was
traced under, which the trace holds as the stat ``tf_op``
(``jit(q1)/jit(filter_table)/cylon.filter/compact/gather``). It gives
each operation the key ``(operator, stage, primitive)``: the outermost
``cylon.`` scope, the next scope inside it (``jit(...)`` skipped; none
where that is another ``cylon.`` operator, which belongs to the outer
one) and the last name, here ``("cylon.filter", "compact",
"gather")``. An operation under no ``cylon.`` scope counts as
``("(unscoped)", "", primitive)``.

:func:`reduce_scopes` sums the device seconds of the ``XLA Ops`` line
inside the ``bench.window`` annotation per key, averaged over the
devices: the seconds ``trace_reduce`` sums into ``ops``, keyed by
operator instead of opcode. ``jax.profiler.ProfileData`` reads the
events but not the stats of their metadata, where ``tf_op`` lives, so
:func:`read_tf_ops` reads those from the protobuf's wire format with
the standard library alone. Each event is attributed through its own
metadata: one HLO text (``%fusion.1 = ...``) recurs across programs
with different ``tf_op``s.
"""

import argparse
import os
import re
import sys

import trace_reduce

#: the operator key of an operation under no ``cylon.`` scope
UNSCOPED = "(unscoped)"
#: the type a profiler appends to a ``tf_op`` (``name:type``)
_TF_TYPE = re.compile(r":[^/]*$")

# XSpace wire format (tsl/profiler/protobuf/xplane.proto): the field
# numbers read below
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_META_ID = 1
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2


def scope_key(tf_op):
    """``(operator, stage, primitive)`` of an operation from its
    ``tf_op`` (see the module's docstring)."""
    parts = _TF_TYPE.sub("", tf_op).split("/")
    for i, part in enumerate(parts[:-1]):
        if part.startswith("cylon."):
            inner = [p for p in parts[i + 1:-1] if not p.startswith("jit(")]
            stage = inner[0] if inner and not inner[0].startswith(
                "cylon.") else ""
            return part, stage, parts[-1]
    return UNSCOPED, "", parts[-1]


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start=0, end=None):
    """The ``(field number, value)`` pairs of one protobuf message in
    ``buf[start:end]``: a varint's value, a length-delimited field's
    ``(start, end)``, a fixed-width field's bytes."""
    end = len(buf) if end is None else end
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for f, v in _fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            value = v
    return key, value


def read_tf_ops(data):
    """``{device plane name: [(event name, tf_op), ...]}`` from a
    serialized ``XSpace``: for every event of the plane's ``XLA Ops``
    lines, in order, the name and the ``tf_op`` stat of the metadata
    the event itself points to (``""`` where it has none). Only device
    planes are decoded."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf):
        if f != _SPACE_PLANES:
            continue
        name, lines, metas, stat_names = None, [], [], {}
        for pf, v in _fields(buf, *plane):
            if pf == _PLANE_NAME:
                name = _text(buf, v)
                if not name.startswith("/device:"):
                    break
            elif pf == _PLANE_LINES:
                lines.append(v)
            elif pf == _PLANE_EVENT_META:
                metas.append(v)
            elif pf == _PLANE_STAT_META:
                sid, meta = _map_entry(buf, v)
                for mf, mv in _fields(buf, *meta):
                    if mf == _META_NAME:
                        stat_names[sid] = _text(buf, mv)
        if name is None or not name.startswith("/device:"):
            continue
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                        None)
        events = {}
        for span in metas:
            mid, meta = _map_entry(buf, span)
            ev_name = tf_op = ""
            for mf, mv in _fields(buf, *meta):
                if mf == _META_NAME:
                    ev_name = _text(buf, mv)
                elif mf == _META_STATS:
                    stat = dict(_fields(buf, *mv))
                    if stat.get(_STAT_META_ID) != tf_op_id:
                        continue
                    if _STAT_STR in stat:
                        tf_op = _text(buf, stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        tf_op = stat_names.get(stat[_STAT_REF], "")
            events[mid] = (ev_name, tf_op)
        ops = []
        for span in lines:
            fields = list(_fields(buf, *span))
            if not any(lf == _LINE_NAME and _text(buf, v) ==
                       trace_reduce.OPS_LINE for lf, v in fields):
                continue
            for lf, ev in fields:
                if lf == _LINE_EVENTS:
                    mid = next((v for ef, v in _fields(buf, *ev)
                                if ef == _EVENT_META_ID), 0)
                    ops.append(events.get(mid, ("", "")))
        out[name] = ops
    return out


def scopes_of(profile, tf_ops, window=trace_reduce.WINDOW):
    """Device seconds per ``(operator, stage, primitive)`` inside the
    window, averaged over the devices, from a
    ``jax.profiler.ProfileData`` and :func:`read_tf_ops` of the same
    trace. Raises ``ValueError`` when the window or every device
    operation is missing, or when ``tf_ops`` does not list a plane's
    operations one for one."""
    win = next(((ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in profile.planes if plane.name == "/host:CPU"
                for line in plane.lines for ev in line.events
                if ev.name == window), None)
    if win is None:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = win
    scopes, devices = {}, 0
    for plane in profile.planes:
        lines = [ln for ln in plane.lines
                 if ln.name == trace_reduce.OPS_LINE]
        if not plane.name.startswith("/device:") or not lines:
            continue
        listed, ran = iter(tf_ops.get(plane.name, ())), False
        for ln in lines:
            for ev in ln.events:
                ev_name, tf_op = next(listed, (None, ""))
                if ev_name != ev.name:
                    raise ValueError(
                        f"tf_ops out of step with {plane.name}'s "
                        f"{trace_reduce.OPS_LINE} at {ev.name[:60]!r}")
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                ran = True
                key = scope_key(tf_op)
                scopes[key] = (scopes.get(key, 0.0)
                               + (min(e, hi) - max(s, lo)) / 1e9)
        devices += ran
    if not devices:
        raise ValueError("no device operation ran inside the window")
    return {k: v / devices for k, v in scopes.items()}


def reduce_scopes(path, window=trace_reduce.WINDOW):
    """:func:`scopes_of` the ``.xplane.pb`` file at ``path``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    return scopes_of(ProfileData.from_serialized_xspace(data),
                     read_tf_ops(data), window)


def scope_seconds(scopes, operator=None, primitive=None):
    """Device seconds of the operations under the operator scope
    ``operator`` (``"cylon.join"``) whose leaf primitive is
    ``primitive`` (``"gather"``); ``None`` matches any."""
    return sum(s for (op, _, prim), s in scopes.items()
               if operator in (None, op) and primitive in (None, prim))


def per_unit_ms(reading, operator=None, primitive=None):
    """A metric reader's value: device milliseconds per unit of work of
    :func:`scope_seconds` over a reading's traced window, averaged over
    the chips; None where the window has no trace or no such time."""
    if reading.trace is None or not reading.units:
        return None
    seconds = scope_seconds(reading.trace.scopes, operator, primitive)
    return seconds / reading.units * 1e3 if seconds else None


def scope_table(scopes, n=20, passes=None):
    """The ``n`` largest entries of ``scopes`` as lines of text, each
    with its share of their sum, then each operator's seconds (and
    milliseconds per pass, given ``passes``) and the unscoped share."""
    total = sum(scopes.values()) or 1.0
    rows = sorted(scopes.items(), key=lambda kv: -kv[1])[:n]
    lines = [f"  {'/'.join(p for p in (op, stage) if p):<24} "
             f"{prim or '-':<24} {s:11.6f} s {100 * s / total:6.2f}%"
             for (op, stage, prim), s in rows]
    ops = {}
    for (op, _, _), s in scopes.items():
        ops[op] = ops.get(op, 0.0) + s
    ops["primitive gather"] = scope_seconds(scopes, primitive="gather")
    for op, s in sorted(ops.items(), key=lambda kv: -kv[1]):
        per = f", {s / passes * 1e3:.3f} ms a pass" if passes else ""
        lines.append(f"  {op}: {s:.6f} s, {100 * s / total:.2f}%{per}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file, or the directory "
                    "a trace was written into")
    ap.add_argument("--passes", type=int, help="whole passes the window "
                    "ran, for milliseconds a pass")
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.find_trace(path)
    print(scope_table(reduce_scopes(path), passes=args.passes))
    return 0


if __name__ == "__main__":
    sys.exit(main())
