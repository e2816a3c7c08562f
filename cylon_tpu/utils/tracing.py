"""Op tracing: wall-clock spans on the host, name scopes on the device.

The reference has no tracer — it inlines ``std::chrono`` timing and
glog INFO lines at op boundaries (shuffle timings ``table.cpp:167-177``;
bench binaries log ``j_t``/``w_t`` per rank,
``cpp/src/examples/bench/table_join_dist_test.cpp:38-56``). The rebuild
formalises that in two halves:

* **Spans** (:func:`span`, :func:`traced`) time host regions. They
  accumulate into the process telemetry registry
  (:mod:`cylon_tpu.telemetry` — one registry for spans, watchdog
  section timings and engine counters, exportable as
  JSONL/Prometheus), and each also emits a
  ``jax.profiler.TraceAnnotation``, so a profiler trace shows it on
  the host timeline beside the device's operations. When the flight
  recorder is armed (``CYLON_TPU_TRACE`` —
  :mod:`cylon_tpu.telemetry.trace`), every span additionally emits
  begin/end events with parent nesting into the trace buffer; with the
  recorder off, the only addition over the pre-recorder span is one
  env read.
* **Scopes** (:func:`scope`) name device work. A compiled query is one
  XLA program, so no host span can say which operator a device
  operation belongs to; a scope is a ``jax.named_scope`` from the one
  vocabulary :data:`SCOPES`, entered while the operator traces. It
  acts at trace time only: the compiled instructions are unchanged and
  every operation's metadata carries the scope, which a profiler trace
  shows as the operation's ``tf_op``
  (``jit(q1)/jit(filter_table)/cylon.filter/compact/gather``). Scopes
  are read from the device trace, spans from the host timeline.

:func:`span`/:func:`timings` are kept as thin wrappers over the
registry so existing callers (and their tests) are untouched;
:class:`SpanStat` remains the aggregate view type.

Caveat that doesn't exist in the reference: JAX dispatch is async, so a
span around a jitted call measures *host orchestration* unless
``sync=`` is given a value to ``block_until_ready`` on.
"""

import contextlib
import functools
from dataclasses import dataclass, field

from cylon_tpu import telemetry
from cylon_tpu.telemetry import trace as _trace
from cylon_tpu.utils.logging import get_logger

#: the telemetry series spans record into (label ``name`` = span name)
SPAN_METRIC = "tracing.span_seconds"

#: The operators whose device work carries a ``cylon.<operator>``
#: scope, each with the stages its body splits into. A nested operator
#: (the sort inside a join) belongs to its outermost ``cylon.`` scope.
SCOPES = {
    "filter": ("mask", "compact"),
    "join": ("keys", "sort", "match", "order", "gather"),
    "groupby": ("sort", "permute", "reduce"),
    "sort": ("keys", "permute"),
    "take": (),
    "project": (),
    "aggregate": (),
    "plan": (),
    "exchange": ("partition", "pack", "all-to-all", "unpack"),
}


@dataclass
class SpanStat:
    count: int = 0
    total_s: float = 0.0
    min_s: float = field(default=float("inf"))
    max_s: float = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)

    def to_json(self) -> dict:
        """Strict-JSON-safe dict: an empty stat's ``min_s`` default of
        ``float("inf")`` would serialise as invalid-JSON ``Infinity``
        (``json.dumps`` emits it happily), so fields normalise through
        the one canonical coercion, :func:`telemetry.json_safe`."""
        return telemetry.json_safe(
            {"count": self.count, "total_s": self.total_s,
             "min_s": self.min_s, "max_s": self.max_s})


@contextlib.contextmanager
def span(name: str, sync=None, cat: "str | None" = None, **targs):
    """Time a named region; optionally block on ``sync`` (any pytree of
    jax arrays) so device work is included in the measurement.

    ``cat``/``**targs`` annotate the flight-recorder event when tracing
    is armed (``cat="stage"`` marks the span as a stage for
    :func:`cylon_tpu.telemetry.trace.critical_path` attribution);
    they cost nothing when it is off. The per-span completion line logs
    at DEBUG — at millions of spans an INFO line per span is pure noise
    on hot paths; aggregate visibility is :func:`report`'s job."""
    import time

    import jax

    t0 = time.perf_counter()
    tok = _trace.begin(name, cat=cat, **targs) if _trace.enabled() \
        else None
    try:
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                if sync is not None:
                    jax.block_until_ready(sync)
                dt = time.perf_counter() - t0
                # the ambient tenant (serve layer) splits the series so
                # per-tenant latency is reportable; outside a tenant
                # scope the labels are {} — the historical series key
                telemetry.timer(SPAN_METRIC, name=name,
                                **telemetry.tenant_labels()).observe(dt)
                get_logger().debug("%s: %.3f ms", name, dt * 1e3)
    finally:
        _trace.end(tok)


def scope(operator: str, stage: "str | None" = None):
    """The ``jax.named_scope`` of ``operator`` (``cylon.<operator>``)
    or, entered inside it, of one of its stages (the stage's bare name,
    so the trace reads ``cylon.join/match``). Both come from
    :data:`SCOPES`; anything else raises ``ValueError``."""
    import jax

    stages = SCOPES.get(operator)
    if stages is None or (stage is not None and stage not in stages):
        raise ValueError(f"no scope {operator!r}/{stage!r} in "
                         "tracing.SCOPES")
    return jax.named_scope(f"cylon.{operator}" if stage is None else stage)


def traced(name: str | None = None):
    """Decorator: run the function under a :func:`span` (host timing)."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def timings(tenant: "str | None" = None) -> dict[str, SpanStat]:
    """Snapshot of accumulated span statistics — a view over the
    telemetry registry's :data:`SPAN_METRIC` series. Series that differ
    only by ``tenant`` label merge per span name; ``tenant=`` restricts
    the view to one tenant's series (the serve layer's per-tenant
    latency slice)."""
    out = {}
    for _, labels, inst in telemetry.instruments(SPAN_METRIC):
        if tenant is not None and labels.get("tenant") != str(tenant):
            continue
        d = inst.dump()  # locked read: count/min/max move together
        if d["count"] and d["min"] is not None:
            s = out.get(labels["name"])
            if s is None:
                out[labels["name"]] = SpanStat(
                    d["count"], float(d["sum"]), float(d["min"]),
                    float(d["max"]))
            else:
                s.count += d["count"]
                s.total_s += float(d["sum"])
                s.min_s = min(s.min_s, float(d["min"]))
                s.max_s = max(s.max_s, float(d["max"]))
    return out


def reset_timings() -> None:
    telemetry.reset("tracing.")


def report(tenant: "str | None" = None) -> str:
    """Human-readable table of span stats, slowest total first. The
    p50/p99 columns come from the shared pow2 histogram buckets
    (:meth:`cylon_tpu.telemetry.registry.Histogram.quantile`) — mean/
    min/max alone hide tail latency, and the tail is where stragglers
    live. ``tenant=`` isolates one tenant's spans from a mixed
    multi-tenant recording (series labeled by the serve layer's
    ambient :func:`cylon_tpu.telemetry.tenant_scope`); the default
    merges every tenant's series per span name."""
    insts: dict[str, list] = {}
    for _, labels, inst in telemetry.instruments(SPAN_METRIC):
        if tenant is not None and labels.get("tenant") != str(tenant):
            continue
        insts.setdefault(labels.get("name", "?"), []).append(inst)
    snap = timings(tenant=tenant)
    if not snap:
        return "(no spans recorded)"
    rows = sorted(snap.items(), key=lambda kv: -kv[1].total_s)
    w = max(len(k) for k, _ in rows)
    lines = [f"{'span':<{w}}  {'count':>6}  {'total ms':>10}  "
             f"{'mean ms':>9}  {'min ms':>8}  {'p50 ms':>8}  "
             f"{'p99 ms':>8}  {'max ms':>8}"]
    for k, s in rows:
        # quantiles over the MERGED bucket ladder when a name has
        # several tenant series (associative by construction)
        inst = telemetry.merge_histograms(insts.get(k, []))
        p50 = inst.quantile(0.5) if inst is not None else None
        p99 = inst.quantile(0.99) if inst is not None else None
        lines.append(
            f"{k:<{w}}  {s.count:>6}  {s.total_s * 1e3:>10.3f}  "
            f"{s.total_s / s.count * 1e3:>9.3f}  {s.min_s * 1e3:>8.3f}  "
            f"{(p50 or 0.0) * 1e3:>8.3f}  {(p99 or 0.0) * 1e3:>8.3f}  "
            f"{s.max_s * 1e3:>8.3f}")
    return "\n".join(lines)
