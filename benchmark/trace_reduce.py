"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

* ``window_s``: the length of the host annotation that marks the
  measured window (``WINDOW``).
* ``busy_s``: per device, the union of the intervals in which an
  operation of the ``XLA Ops`` line ran, inside the window; averaged
  over the devices. (On a TPU that line holds the TensorCore's
  operations one after another; asynchronous copies and collectives in
  flight are on other lines and are not counted as busy.)
* ``ops``: device seconds per category, averaged over the devices. The
  category is what the trace itself says of each operation: a TPU
  trace names an operation by its HLO text (``%sort.6 = (...) sort(...)``),
  whose opcode is the category, with a fusion's kind
  (``fusion:kCustom``) and an asynchronous operation's own name less
  its number (``copy-start``); a bare name (``sort.0`` on the CPU) loses
  its number.
* ``idle``: device idle seconds inside the window, each gap named by
  the innermost host span running at its middle: the program's span
  (those ``span_names`` lists) or the benchmark's (``bench.*``), then
  the innermost
  host event of any kind, as ``"plan.fetch: np.asarray(jax.Array)"``;
  averaged over the devices.
* ``scopes``: device seconds per operator scope, ``(operator, stage,
  primitive)``, averaged over the devices (``trace_scopes``; filled by
  :func:`reduce_file`).

Only ``jax.profiler.ProfileData`` and the standard library are used, so
the reduction needs nothing beyond JAX.
"""

import dataclasses
import glob
import heapq
import os
import re

#: the host annotation around the measured window
WINDOW = "bench.window"
#: the line of a device plane that holds one event per operation run
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[.:]\d+$")
#: ``%name = <shape> opcode(``: the instruction's name and opcode
_HLO = re.compile(r"%?([\w\-.]+) = .*?\s([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=(\w+)")
_ASYNC = ("async-start", "async-update", "async-done")
_NONE = "(no host span)"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    ops: dict
    idle: dict
    scopes: dict = dataclasses.field(default_factory=dict)

    def op_seconds(self, pattern):
        """Device seconds (per device) of the operations whose category
        matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.ops.items() if rx.search(n))

    def breakdown(self, n=10):
        """The contract's ``breakdown``: the operations that took most
        device time and the host activities that left the device idle
        longest, each as ``[name, seconds]``."""
        def top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:n]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.idle)}


def find_trace(logdir):
    """The one ``.xplane.pb`` file a trace into ``logdir`` wrote."""
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one trace under {logdir}, "
                                f"found {len(found)}")
    return found[0]


def _union(intervals):
    """Disjoint, sorted intervals covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def category(name):
    """The category of a device operation, from its name in the
    trace."""
    m = _HLO.match(name)
    if m is None:
        return _SUFFIX.sub("", name)
    inst, op = m.groups()
    if op in _ASYNC:
        return _SUFFIX.sub("", inst)
    if op == "fusion":
        kind = _KIND.search(name)
        return f"fusion:{kind.group(1)}" if kind else op
    return op


def _innermost(spans, points):
    """For each of the ascending ``points``, the name of the host span
    covering it that started last (on one thread, the innermost), or
    ``_NONE``. One sweep: spans enter a heap as the points
    pass their start, and leave it once a point passes their end."""
    spans = sorted(spans)
    heap, out, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            s, e, name = spans[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else _NONE)
    return out


def reduce_profile(profile, window=WINDOW, span_names=()):
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`.

    Device planes are those named ``/device:...`` that have an
    ``XLA Ops`` line; host spans are the events of the ``/host:CPU``
    plane. Raises ``ValueError`` when the window annotation or every
    device operation is missing.
    """
    host, win = [], None
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name == window:
                    win = span
                elif ev.duration_ns > 0:
                    host.append(span)
    if win is None:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = win[0], win[1]
    host = [h for h in host if h[1] > lo and h[0] < hi]
    named = [h for h in host
             if h[2] in span_names or h[2].startswith("bench.")]

    devices, busy_ns = 0, 0.0
    ops, idle = {}, {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        spans = []
        for ln in lines:
            for ev in ln.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                spans.append((s, e))
                cat = category(ev.name)
                ops[cat] = ops.get(cat, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        if not spans:
            continue
        devices += 1
        merged = _union(_clip(spans, lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        mids = [(gs + ge) / 2 for gs, ge in gaps]
        names = [n if n == m or m == _NONE else f"{m}: {n}"
                 for m, n in zip(_innermost(named, mids),
                                 _innermost(host, mids))]
        for (gs, ge), name in zip(gaps, names):
            idle[name] = idle.get(name, 0.0) + (ge - gs) / 1e9
    if not devices:
        raise ValueError("no device operation ran inside the window")

    def per_device(d):
        return {k: v / devices for k, v in d.items()}

    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=busy_ns / 1e9 / devices, devices=devices,
                        ops=per_device(ops), idle=per_device(idle))


def reduce_file(path, window=WINDOW, span_names=()):
    """:func:`reduce_profile` of the ``.xplane.pb`` file at ``path``,
    with its ``scopes`` from ``trace_scopes``."""
    from jax.profiler import ProfileData

    import trace_scopes

    with open(path, "rb") as f:
        data = f.read()
    profile = ProfileData.from_serialized_xspace(data)
    summary = reduce_profile(profile, window, span_names)
    summary.scopes = trace_scopes.scopes_of(
        profile, trace_scopes.read_tf_ops(data), window)
    return summary
