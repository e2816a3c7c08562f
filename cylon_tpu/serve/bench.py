"""serve_bench: N concurrent clients replaying a mixed TPC-H workload
against one :class:`~cylon_tpu.serve.ServeEngine`.

The serving acceptance harness (ROADMAP item 4): ``--clients N``
(default 8) client threads, each its own tenant/session, fire a mixed
TPC-H query stream (default mix q1/q3/q5/q6 — groupby-heavy, 3-way
join, 6-way join, scalar aggregate) at a shared engine holding the
TPC-H tables RESIDENT on one mesh. Every result is compared against a
single-query oracle (the same query run once, alone, before serving
starts), so the run proves correctness under concurrency, not just
liveness. One JSON record lands on stdout with the schema pinned by
:data:`REQUIRED_SERVE_FIELDS` (and ``tests/test_bench_guard.py``):
p50/p99 request latency from the ``serve.request_seconds`` histogram
quantiles, throughput (qps), plan-cache hit rate (the shared
compiled-plan cache means N clients with one query shape pay one
trace), and rejected/expired/error counts.

Run (CPU-host mesh, the same 8-virtual-device topology tier-1 uses)::

    python -m cylon_tpu.serve.bench --clients 8

Knobs: ``--requests`` per client (default 2), ``--sf`` scale factor
(default 0.002), ``--schedule roundrobin|priority``, ``--slo`` seconds
(default unbounded), ``--max-queue``, ``--seed``, plus the
``CYLON_TPU_SERVE_*`` env family (``docs/serving.md``).

``--refresh`` runs the incremental-view leg instead (ISSUE 18,
``docs/views.md``): RF1-style append rounds (``--appends``,
``--delta-sf``) interleaved with concurrent ``read_view`` readers
against registered q1/q3/q5/q6 materialized views — every read audited
post-hoc against a pinned-generation oracle — emitting one record
pinned by :data:`REQUIRED_REFRESH_FIELDS` (incremental refresh wall vs
full-recompute wall, gated ``speedup >= 2``, ``oracle_mismatches``
gated 0).
"""

import argparse
import json
import os
import sys
import threading
import time

# CPU-host mesh by default (like tests/conftest.py): harmless on a real
# TPU backend — the flag only shapes the *host* platform's device count
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np

#: serve-record fields the serving trajectory depends on — emit asserts
#: them and ``tests/test_bench_guard.py`` pins the set, so a refactor
#: cannot silently drop the latency quantiles or the cache-hit column.
REQUIRED_SERVE_FIELDS = frozenset({
    "metric", "clients", "requests_total", "tenants", "schedule",
    "p50_s", "p99_s", "qps", "cache_hit_rate", "rejected", "errors",
    "expired", "oracle_mismatches", "shed", "journal_replayed",
    "recoveries", "degraded",
    # attribution columns (ISSUE 9): every serve artifact carries the
    # slowest request's ANALYZE profile and the run's HBM high-water
    # mark, not just p50/p99
    "slowest_profile", "peak_live_bytes",
    # windowed-observability columns (ISSUE 14): the sliding-window
    # p99 (from the metric-history ring — within one pow2 bucket of
    # the exact per-request quantile, which rides as p99_exact_s) and
    # the worst SLO burn rate any tenant reached (0 when burn
    # accounting is unarmed)
    "windowed_p99_s", "slo_burn",
    # dedup-layer columns (ISSUE 19): the versioned result cache and
    # micro-batched dispatch counters — 0 on the bare-callable replay
    # (no fingerprints), live on the --hot-mix leg — pinned so a
    # refactor cannot silently drop the dedup plane's accounting
    "result_cache_hits", "result_cache_misses",
    "result_cache_invalidations", "coalesced",
})

#: hot-mix-record fields (ISSUE 19): the ``--hot-mix`` acceptance is
#: only auditable if every record pins the measured hot-path QPS
#: against the single-engine uncached baseline (their ratio is the
#: ``qps_multiplier`` the acceptance gates at >= 10x), the hot-phase
#: cache hit rate, the dedup counters, and the staleness audit (an
#: append between submissions MUST force a re-execution — 0 stale
#: results). ``tests/test_bench_guard.py`` pins the set; main()
#: asserts it before emitting.
REQUIRED_HOTMIX_FIELDS = frozenset({
    "metric", "engines", "clients", "requests_total", "completed",
    "baseline_qps", "hot_qps", "qps_multiplier", "p50_s", "p99_s",
    "cache_hit_rate", "shed", "coalesced", "result_cache_hits",
    "result_cache_misses", "result_cache_invalidations",
    "oracle_mismatches", "stale_results", "errors",
})

#: fleet-record fields (ISSUE 15): the ``--fleet`` acceptance is only
#: auditable if every record pins the engine count, the failover and
#: replay counters, the lost-ack and double-execution audits (both
#: MUST be 0) and the p99 before/during/after the mid-run kill.
#: ``tests/test_bench_guard.py`` pins the set; main() asserts it
#: before emitting.
REQUIRED_FLEET_FIELDS = frozenset({
    "metric", "engines", "clients", "requests_total", "completed",
    "failovers", "replayed", "lost_acks", "routed", "deduped",
    "retry_deduped", "double_executions", "oracle_mismatches",
    "errors", "p99_before_s", "p99_during_s", "p99_after_s",
})

#: extra fields a ``--fleet-trace`` record must carry (ISSUE 20): the
#: stitched-timeline artifact is only auditable if the record pins
#: where the Chrome trace landed, how many spans and engine tracks it
#: stitched, the clock-handshake jitter bound the alignment rests on,
#: and the failover replay hops the headline trace id crossed.
#: ``tests/test_bench_guard.py`` pins the set; main() asserts it.
REQUIRED_FLEET_TRACE_FIELDS = frozenset({
    "trace_path", "spans", "engines_stitched", "offset_jitter_s",
    "replay_hops",
})

#: refresh-record fields (ISSUE 18): the ``--refresh`` acceptance is
#: only auditable if every record pins the incremental-refresh wall
#: against the from-scratch recompute wall (their ratio is the
#: ``speedup`` the acceptance gates at >= 2x), the generation lag the
#: concurrent readers observed, and the oracle audit (MUST be 0
#: mismatches). ``tests/test_bench_guard.py`` pins the set; main()
#: asserts it before emitting.
REQUIRED_REFRESH_FIELDS = frozenset({
    "metric", "sf", "delta_sf", "views", "appends", "refreshes",
    "delta_rows_total", "refresh_wall_s", "recompute_wall_s",
    "speedup", "generation_lag", "oracle_mismatches", "reads_total",
    "errors",
})

#: default mixed workload: groupby-heavy scan, 3-way join + top-k,
#: 6-way join, a scalar aggregate, and a two-phase global aggregate
#: (q14's promo ratio needs a global merge scalar — its spill path is
#: the ISSUE 16 two-phase plan) — five distinct shapes so the schedule
#: interleaves genuinely different pipelines
DEFAULT_MIX = ("q1", "q3", "q5", "q6", "q14")

#: the ``--refresh`` workload (ISSUE 18): the four mix shapes whose
#: fallback merge is directly view-maintainable — groupby+wmean (q1),
#: concat+resort top-k (q3), associative groupby (q5), scalar sum
#: (q6). Two-phase views keep a phase-1 partial as state and need a
#: partial-returning query fn — they ride tests/test_views.py, not
#: this leg.
REFRESH_MIX = ("q1", "q3", "q5", "q6")


def _emit_record(line: dict):
    """The ONE stdout sink for serve bench records: attaches the
    telemetry ``metrics`` block like every other bench driver (schema
    lint in tests/test_bench_guard.py). Telemetry must never fail a
    bench."""
    line = dict(line)
    try:
        from cylon_tpu import telemetry

        line["metrics"] = telemetry.bench_metrics()
    except Exception as e:  # pragma: no cover - import-time breakage
        line["metrics"] = {"telemetry_error": f"{type(e).__name__}: {e}"}
    print(json.dumps(line))


def _materialize(out):
    """Host-side result of a query call: DataFrames/Tables gather to
    pandas, scalars to float — the client-visible payload."""
    if hasattr(out, "to_pandas"):
        return out.to_pandas().reset_index(drop=True)
    arr = np.asarray(out)
    if arr.ndim == 0:
        return float(arr)
    return arr


def _results_match(got, want) -> bool:
    """Order-insensitive equality between a served result and its
    single-query oracle (float columns to 1e-9 rtol)."""
    from cylon_tpu.testing import compare_results

    return compare_results(got, want, ignore_order=True)[0] is None


def _staged_query(cq, resident, env):
    """A two-step generator query for the scheduler: step 1 runs the
    compiled program (dispatch + overflow check), step 2 materialises
    the result to the host — so while one request's result fetch (or
    XLA in-flight work) drains, the schedule is already dispatching the
    next tenant's step."""

    def run():
        out = cq(resident, env=env)
        yield  # step boundary: result fetch happens on the next sweep
        return _materialize(out)

    return run


def _mk_resident(env, data):
    """Lay the TPC-H tables out on the mesh ONCE and register them in
    the catalog (``tpch/<name>``) — the shared resident store every
    request reads; returns the {name: DataFrame} mapping queries take."""
    from cylon_tpu import tpch
    from cylon_tpu.frame import DataFrame
    from cylon_tpu.parallel import scatter_table

    resident = {}
    for name, df in tpch.ingest(data).items():
        if env is not None and env.is_distributed:
            df = DataFrame._wrap(scatter_table(env, df.table))
        resident[name] = df
    return resident


def _fault_storm(engine, http_addr, requests: int = 8,
                 tenant: str = "storm") -> dict:
    """The ISSUE 14 measured acceptance: drive ONE tenant into a
    deadline storm against a live engine and watch the observability
    plane tell the story — ``/health`` flips ok → unhealthy (reasons
    naming the breaker and the burning tenant's SLO), sheds and
    breaker transitions land in ``/events`` in order, and after the
    cooldown + the storm window aging out, ``/health`` recovers to ok.

    Polls the verdict over HTTP when the introspection endpoint is
    armed (the router's view), falling back to ``engine.health()``."""
    import urllib.request

    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import events as _events

    def verdict():
        if http_addr is not None:
            url = "http://%s:%d/health" % http_addr
            with urllib.request.urlopen(url, timeout=10) as r:
                return json.loads(r.read())
        return engine.health()

    cursor = _events.since(0)["cursor"]
    transitions = [verdict()["status"]]
    unhealthy_reasons = None
    peak_burn = 0.0

    def note(v):
        nonlocal unhealthy_reasons, peak_burn
        if v["status"] != transitions[-1]:
            transitions.append(v["status"])
        if v["status"] == "unhealthy" and unhealthy_reasons is None:
            unhealthy_reasons = list(v["reasons"])
        worst = (v.get("components", {}).get("slo") or {}).get("worst")
        if worst and worst["burn"] > peak_burn:
            peak_burn = worst["burn"]

    def slow():
        time.sleep(0.3)
        return None

    t0 = time.perf_counter()
    tickets = []
    for _ in range(int(requests)):
        try:
            tickets.append(engine.submit(slow, tenant=tenant,
                                         slo=0.02))
        except Exception:
            pass  # breaker may already be shedding: that IS the storm
        note(verdict())
    for tk in tickets:
        try:
            tk.result(30)
        except Exception:
            pass
        note(verdict())
    # keep poking the front door while open so sheds land in /events
    shed_probe_errors = 0
    deadline = time.monotonic() + 60
    recovered = False
    while time.monotonic() < deadline:
        v = verdict()
        note(v)
        if v["status"] == "ok" and "unhealthy" in transitions:
            recovered = True
            break
        try:
            # good traffic probes the half-open breaker and re-earns
            # the SLO budget once the storm ages out of the window
            engine.submit(lambda: 1, tenant=tenant,
                          slo=30.0).result(30)
        except Exception:
            shed_probe_errors += 1
        time.sleep(0.25)
    replay = _events.since(cursor)
    kinds = [e["kind"] for e in replay["events"]]
    seqs = [e["seq"] for e in replay["events"]]
    return {
        "tenant": tenant,
        "requests": int(requests),
        "wall_s": round(time.perf_counter() - t0, 3),
        "health_transitions": transitions,
        "unhealthy_reasons": unhealthy_reasons,
        "recovered": recovered,
        "peak_burn": round(peak_burn, 4),
        # recovery probes the open/half-open breaker refused — how
        # hard the front door pushed back during the recovery loop
        "recovery_probes_shed": shed_probe_errors,
        "storm_errors": telemetry.total("serve.errors"),
        "storm_shed": telemetry.total("serve.shed"),
        "breaker_trips": telemetry.total("serve.breaker_trips"),
        "events_replayed": len(kinds),
        "event_kinds": sorted(set(kinds)),
        "events_in_order": seqs == sorted(seqs),
        "events_dropped": replay["dropped"],
    }


def run_bench(clients: int = 8, requests: int = 2, sf: float = 0.002,
              schedule: str = "roundrobin", slo: "float | None" = None,
              max_queue: "int | None" = None, seed: int = 0,
              mix=DEFAULT_MIX, slo_target: "float | None" = None,
              slo_latency: "float | None" = None,
              slo_windows: "tuple | None" = None,
              storm: int = 0) -> dict:
    import cylon_tpu as ct
    from cylon_tpu import catalog, telemetry, tpch, watchdog
    from cylon_tpu.errors import ResourceExhausted
    from cylon_tpu.serve import ServeEngine, ServePolicy
    from cylon_tpu.serve.admission import default_policy
    from cylon_tpu.telemetry import timeseries
    from cylon_tpu.tpch import dbgen

    env = ct.CylonEnv(ct.TPUConfig())
    data = dbgen.generate(sf, seed)
    resident = _mk_resident(env, data)
    for name, df in resident.items():
        catalog.put_table(f"tpch/{name}", df.table)

    base = default_policy()
    if storm and slo_target is None and base.slo_target is None:
        # the fault-storm acceptance needs burn accounting armed and
        # windows short enough to watch /health recover inside one
        # bench run
        slo_target = 0.99
        slo_windows = slo_windows or (10.0, 30.0)
    policy = ServePolicy(
        max_queue=max_queue if max_queue is not None else base.max_queue,
        default_slo=slo if slo and slo > 0 else base.default_slo,
        schedule=schedule,
        breaker_fails=base.breaker_fails,
        breaker_window=base.breaker_window,
        breaker_cooldown=base.breaker_cooldown,
        slo_target=(slo_target if slo_target is not None
                    else base.slo_target),
        slo_latency=(slo_latency if slo_latency is not None
                     else base.slo_latency),
        slo_windows=tuple(slo_windows or base.slo_windows),
        burn_critical=base.burn_critical)
    # baseline sample for the windowed-p99 column: the whole replay
    # lands in one history delta slot
    timeseries.sample(force=True)

    # single-query oracles: each mix query runs ONCE, alone, through
    # the same shared compiled plan — every concurrent result must
    # reproduce these exactly (and the serving run then hits the warm
    # cross-request plan cache, which is the point of sharing it)
    compiled = {q: tpch.compiled(q) for q in mix}
    oracles = {q: _materialize(compiled[q](resident, env=env))
               for q in mix}

    engine = ServeEngine(env, policy)
    mismatches = []
    rejected_local = [0]
    all_tickets = []  # (query, ticket) across every client thread
    lock = threading.Lock()

    def client(i: int):
        # under the priority schedule, odd clients are weight-2
        # tenants — they take two steps per sweep to the others' one
        prio = 2 if (schedule == "priority" and i % 2) else 1
        tenant = f"tenant{i}"
        with engine.session(tenant, priority=prio,
                            tables=[f"tpch/{n}" for n in resident]) as s:
            tickets = []
            for r in range(requests):
                q = mix[(i + r) % len(mix)]
                try:
                    tk = s.submit(_staged_query(compiled[q],
                                                resident, env))
                    tickets.append((q, tk))
                    with lock:
                        all_tickets.append((q, tk))
                except ResourceExhausted:
                    with lock:
                        rejected_local[0] += 1
            for q, tk in tickets:
                try:
                    got = tk.result()
                except Exception as e:
                    with lock:
                        mismatches.append((tenant, q,
                                           f"{type(e).__name__}: {e}"))
                    continue
                if not _results_match(got, oracles[q]):
                    with lock:
                        mismatches.append((tenant, q, "result mismatch"))

    # the whole replay runs inside the named serve_request watchdog
    # section: a hung engine dumps stacks + raises under an ambient
    # deadline instead of wedging the driver silently
    t0 = time.perf_counter()
    with watchdog.watched_section("serve_request",
                                  detail="serve_bench replay"):
        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"serve-client-{i}")
                   for i in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    wall = time.perf_counter() - t0
    http_addr = engine.http_address  # captured before close unbinds
    # close the replay's windowed slot + read the healthy-phase gate
    # counters BEFORE any storm phase muddies them (storm errors are
    # INTENDED; they ride the storm block, not the pass/fail columns)
    timeseries.sample(force=True)
    windowed_p99 = timeseries.history().quantile(
        "serve.request_seconds", 0.99)
    exact_walls = sorted(
        tk.finished - tk.submitted for _, tk in all_tickets
        if tk.finished is not None and tk.state == "done")
    p99_exact = (float(np.quantile(np.asarray(exact_walls), 0.99))
                 if exact_walls else None)
    healthy_errors = telemetry.total("serve.errors")
    healthy_shed = telemetry.total("serve.shed")
    healthy_rejected = telemetry.total("serve.rejected")
    healthy_expired = telemetry.total("serve.expired")
    # ... and the latency/throughput columns: the cumulative request
    # histogram, completed count and tenant set are REPLAY-ONLY too —
    # read after the storm they would absorb the storm's expired
    # walls + recovery probes and overstate qps against the
    # replay-only wall
    hist = telemetry.merge_histograms(
        [inst for _, _, inst in
         telemetry.instruments("serve.request_seconds")])
    completed = telemetry.total("serve.completed")
    n_tenants = len(engine.tenant_stats())

    storm_block = (_fault_storm(engine, http_addr, requests=storm)
                   if storm else None)
    worst = engine.slo_report().get("worst")
    engine.close(wait=True)

    cache = engine.plan_cache_stats()
    record = {
        "metric": "serve_bench_tpch_mix",
        "clients": clients,
        "requests_total": clients * requests,
        "tenants": n_tenants,
        "schedule": schedule,
        "sf": sf,
        "wall_s": round(wall, 3),
        "qps": round(completed / wall, 3) if wall > 0 else None,
        "p50_s": (round(hist.quantile(0.5), 4)
                  if hist is not None and hist.count else None),
        "p99_s": (round(hist.quantile(0.99), 4)
                  if hist is not None and hist.count else None),
        "completed": completed,
        "rejected": healthy_rejected,
        "errors": healthy_errors,
        "expired": healthy_expired,
        # robustness columns (ISSUE 8): load shed by the admission
        # layer (queue_full / breaker), journal replays and recoveries
        # — 0 on a healthy fault-free replay, pinned so a chaos run's
        # sheds/replays ride the trajectory
        "shed": healthy_shed,
        # windowed-observability columns (ISSUE 14): sliding-window
        # p99 from the metric-history ring (bucket resolution — the
        # exact client-side quantile rides as p99_exact_s for the
        # within-one-bucket pin) and the worst tenant burn rate
        "windowed_p99_s": (round(windowed_p99, 4)
                           if windowed_p99 is not None else None),
        "p99_exact_s": (round(p99_exact, 4)
                        if p99_exact is not None else None),
        # the worst burn any tenant REACHED during the run (a storm's
        # peak survives the recovery that the live read decays with)
        "slo_burn": max(
            worst["burn"] if worst is not None else 0.0,
            storm_block["peak_burn"] if storm_block else 0.0),
        "journal_replayed": telemetry.total("serve.journal_replayed"),
        "recoveries": telemetry.total("serve.recoveries"),
        # graceful degradation (ISSUE 10): requests that completed
        # through the OOM→spill fallback — 0 on a healthy replay,
        # pinned so degraded completions ride the trajectory
        "degraded": telemetry.total("serve.degraded"),
        "cache_hit_rate": round(cache["hit_rate"], 4),
        "cache_hits": cache["hits"],
        "cache_misses": cache["misses"],
        # dedup-layer counters (ISSUE 19): result-cache traffic and
        # coalesced fan-outs — structurally 0 here (this replay
        # submits bare callables, which have no fingerprint), live on
        # the --hot-mix leg; pinned so the columns always ride
        "result_cache_hits": int(
            telemetry.total("serve.result_cache_hits")),
        "result_cache_misses": int(
            telemetry.total("serve.result_cache_misses")),
        "result_cache_invalidations": int(
            telemetry.total("serve.result_cache_invalidations")),
        "coalesced": int(telemetry.total("serve.coalesced")),
        "oracle_mismatches": len(mismatches),
        "mismatch_detail": mismatches[:8],
        "resident_tables": len(resident),
    }
    # attribution (ISSUE 9): the slowest completed request's ANALYZE
    # profile rides the artifact — a p99 regression in the trajectory
    # names its stages, operators and bytes instead of being a bare
    # number — plus the run's HBM high-water mark
    slowest = None
    for q, tk in all_tickets:
        if tk.finished is None or tk.state != "done":
            continue
        w = tk.finished - tk.submitted
        if slowest is None or w > slowest[0]:
            slowest = (w, q, tk)
    prof = slowest[2].profile() if slowest is not None else None
    if prof is not None:
        prof["query"] = slowest[1]
    record["slowest_profile"] = prof
    record["peak_live_bytes"] = telemetry.memory.peak_live_bytes()
    if storm_block is not None:
        record["storm"] = storm_block
    if http_addr is not None:
        record["http_url"] = "http://%s:%d" % http_addr
    return record


def run_hotmix_bench(clients: int = 64, requests: int = 4,
                     sf: float = 0.002, seed: int = 0,
                     mix=DEFAULT_MIX, engines: int = 2) -> dict:
    """The ISSUE 19 measured acceptance: N concurrent clients replay a
    HOT mix (identical fingerprints, stable tables) through the
    FleetRouter twice — once against a single uncached engine
    (coalescing and both result caches disabled: every request
    executes), once against the full dedup plane (engine + router
    caches on, coalescing on, warmed) — and the record gates the
    hot-over-baseline QPS multiplier at >= 10x. A mid-probe append
    then proves the staleness contract: the very next submission of an
    affected query must MISS and re-execute (0 stale results). Every
    result, both phases, is oracle-checked."""
    import cylon_tpu as ct
    from cylon_tpu import catalog, telemetry, tpch
    from cylon_tpu.errors import ResourceExhausted
    from cylon_tpu.serve import ServeEngine
    from cylon_tpu.serve.fleet import (QUERY_READ_SETS, EngineUnavailable,
                                       FleetRouter, LocalEngineClient,
                                       _mk_fleet_query)
    from cylon_tpu.tpch import dbgen

    env = ct.CylonEnv(ct.TPUConfig())
    data = dbgen.generate(sf, seed)
    resident = _mk_resident(env, data)
    for name, df in resident.items():
        catalog.put_table(f"tpch/{name}", df.table)
    mix = tuple(mix)
    # oracles warm the shared compiled-plan cache for BOTH phases
    # equally — the multiplier measures the dedup plane, not compile
    # amortisation
    compiled = {q: tpch.compiled(q) for q in mix}
    oracles = {q: _materialize(compiled[q](resident, env=env))
               for q in mix}

    def mk_fleet(n_engines: int):
        engs, clis = [], []
        for i in range(n_engines):
            e = ServeEngine(env)
            for q in mix:
                reads = QUERY_READ_SETS.get(q, tuple(resident))
                e.register_query(
                    q, _mk_fleet_query(compiled[q], resident, env),
                    tables=[f"tpch/{nm}" for nm in reads
                            if nm in resident])
            engs.append(e)
            clis.append(LocalEngineClient(e, f"hot{i}"))
        return engs, FleetRouter(clis, poll_interval=0.25)

    def drive(router, n_requests: int, label: str) -> dict:
        mismatches: list = []
        errors: list = []
        shed = [0]
        walls: "list[float]" = []
        lock = threading.Lock()

        def client(i: int):
            tenant = f"tenant{i}"
            for r in range(n_requests):
                q = mix[(i + r) % len(mix)]
                s0 = time.monotonic()
                try:
                    got = router.submit(q, tenant=tenant).result(600)
                except Exception as e:
                    with lock:
                        if isinstance(e, (ResourceExhausted,
                                          EngineUnavailable)):
                            shed[0] += 1
                        errors.append(
                            (tenant, q, f"{type(e).__name__}: {e}"))
                    continue
                w = time.monotonic() - s0
                with lock:
                    walls.append(w)
                if not _results_match(got, oracles[q]):
                    with lock:
                        mismatches.append((tenant, q, label))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"hotmix-{label}-{i}")
                   for i in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        ws = sorted(walls)
        return {
            "wall_s": wall, "completed": len(walls),
            "qps": (len(walls) / wall) if wall > 0 else None,
            "p50_s": (float(np.quantile(np.asarray(ws), 0.5))
                      if ws else None),
            "p99_s": (float(np.quantile(np.asarray(ws), 0.99))
                      if ws else None),
            "shed": shed[0], "mismatches": mismatches,
            "errors": errors,
        }

    knobs = {"CYLON_TPU_SERVE_RESULT_CACHE_BYTES": "0",
             "CYLON_TPU_SERVE_COALESCE": "0",
             "CYLON_TPU_FLEET_RESULT_CACHE_BYTES": "0"}
    saved = {k: os.environ.get(k) for k in knobs}

    # ---- phase 1: the single-engine uncached baseline (dedup plane
    # OFF end to end — every submission executes). Fewer requests per
    # client than the hot phase: QPS is a rate, and the baseline only
    # needs a stable one
    base_requests = max(1, requests // 2)
    os.environ.update(knobs)
    try:
        engs, router = mk_fleet(1)
        try:
            base = drive(router, base_requests, "baseline")
        finally:
            router.close()
            for e in engs:
                e.close(wait=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # ---- phase 2: the full dedup plane (engine + router caches,
    # coalescing), warmed with one execution per mix query so the
    # measured window is the HOT path
    engs, router = mk_fleet(engines)
    try:
        for q in mix:
            got = router.submit(q, tenant="warmup").result(600)
            if not _results_match(got, oracles[q]):
                base["mismatches"].append(("warmup", q, "warmup"))
        hits0 = telemetry.total("fleet.result_cache_hits") + \
            telemetry.total("serve.result_cache_hits")
        hot = drive(router, requests, "hot")
        hits1 = telemetry.total("fleet.result_cache_hits") + \
            telemetry.total("serve.result_cache_hits")
        hit_rate = ((hits1 - hits0) / hot["completed"]
                    if hot["completed"] else 0.0)

        # ---- the staleness probe: append one row to lineitem, then
        # re-submit a lineitem query — the dedup plane must MISS
        # (invalidation reached both caches) and the re-execution must
        # still match the oracle. A hit here would be a STALE RESULT.
        probe_q = next((q for q in mix if "lineitem"
                        in QUERY_READ_SETS.get(q, ("lineitem",))),
                       mix[0])
        cols = catalog.get_table("tpch/lineitem").column_names
        row = {c: np.asarray(data["lineitem"][c][:1]) for c in cols}
        misses0 = telemetry.total("fleet.result_cache_misses") + \
            telemetry.total("serve.result_cache_misses")
        catalog.append("tpch/lineitem", row, env=env)
        stale = 0
        try:
            got = router.submit(probe_q, tenant="probe").result(600)
        except Exception as e:
            hot["errors"].append(("probe", probe_q,
                                  f"{type(e).__name__}: {e}"))
        else:
            misses1 = telemetry.total("fleet.result_cache_misses") + \
                telemetry.total("serve.result_cache_misses")
            # resident inputs are engine-side frames (the catalog
            # entry only versions them), so the re-run still matches
            # the oracle; what MUST have changed is the miss count
            if misses1 <= misses0:
                stale += 1
            if not _results_match(got, oracles[probe_q]):
                hot["mismatches"].append(("probe", probe_q, "probe"))
    finally:
        router.close()
        for e in engs:
            e.close(wait=True)

    mismatches = base["mismatches"] + hot["mismatches"]
    errors = base["errors"] + hot["errors"]
    record = {
        "metric": "serve_hotmix_fleet",
        "engines": engines,
        "clients": clients,
        "requests_total": clients * requests,
        "completed": hot["completed"],
        "sf": sf,
        "mix": list(mix),
        "baseline_requests_total": clients * base_requests,
        "baseline_completed": base["completed"],
        "baseline_wall_s": round(base["wall_s"], 3),
        "baseline_qps": (round(base["qps"], 3)
                         if base["qps"] else None),
        "baseline_p50_s": (round(base["p50_s"], 4)
                           if base["p50_s"] is not None else None),
        "baseline_p99_s": (round(base["p99_s"], 4)
                           if base["p99_s"] is not None else None),
        "wall_s": round(hot["wall_s"], 3),
        "hot_qps": round(hot["qps"], 3) if hot["qps"] else None,
        "qps_multiplier": (round(hot["qps"] / base["qps"], 2)
                           if hot["qps"] and base["qps"] else None),
        "p50_s": (round(hot["p50_s"], 4)
                  if hot["p50_s"] is not None else None),
        "p99_s": (round(hot["p99_s"], 4)
                  if hot["p99_s"] is not None else None),
        "cache_hit_rate": round(hit_rate, 4),
        "shed": base["shed"] + hot["shed"],
        "coalesced": int(telemetry.total("serve.coalesced")),
        "result_cache_hits": int(
            telemetry.total("fleet.result_cache_hits")
            + telemetry.total("serve.result_cache_hits")),
        "result_cache_misses": int(
            telemetry.total("fleet.result_cache_misses")
            + telemetry.total("serve.result_cache_misses")),
        "result_cache_invalidations": int(
            telemetry.total("fleet.result_cache_invalidations")
            + telemetry.total("serve.result_cache_invalidations")),
        "stale_results": stale,
        "oracle_mismatches": len(mismatches),
        "mismatch_detail": mismatches[:8],
        "errors": len(errors),
        "error_detail": errors[:8],
    }
    return record


def _refresh_keep(mix) -> dict:
    """Per-table column keep-sets for the refresh workload: the union
    of the mix's manifests plus the order keys the RF1 append stream
    offsets — SF1 stays host-feasible because unreferenced columns
    (the wide comment strings above all) never generate."""
    from cylon_tpu.tpch.manifest import MANIFEST

    keep: dict = {}
    for q in mix:
        for t, cols in MANIFEST[q].items():
            keep.setdefault(t, set()).update(cols)
    keep.setdefault("orders", set()).add("o_orderkey")
    keep.setdefault("lineitem", set()).add("l_orderkey")
    return {t: frozenset(c) for t, c in keep.items()}


def _mk_view_query(q):
    """The view query fn for one mix query: the engine's partitioned
    EAGER fallback over whatever tables it is handed — the same
    execution path for the delta run, the initial materialization and
    the from-scratch oracle, so the refresh-vs-recompute walls compare
    like with like. Small inputs (a delta) skip the partition split."""
    from cylon_tpu import fallback

    def qf(tables):
        data = {name: {c: df[c].to_numpy() for c in df.columns}
                for name, df in tables.items()}
        li = data.get("lineitem")
        rows = len(next(iter(li.values()))) if li else 0
        return fallback.tpch_fallback(
            q, data, compiled=False,
            n_partitions=1 if rows < 100_000 else None)

    return qf


def run_refresh_bench(sf: float = 0.05, delta_sf: "float | None" = None,
                      rounds: int = 2, clients: int = 4, seed: int = 0,
                      mix=REFRESH_MIX) -> dict:
    """The ISSUE 18 acceptance harness: TPC-H RF1-style appends (new
    orders arriving WITH their lineitems — join-closed by
    construction) interleaved with the q1/q3/q5/q6 mix served as
    incremental materialized views.

    Per round: one key-offset dbgen delta appends to the resident
    ``orders`` and ``lineitem`` tables (generation bumps), every view
    refreshes INCREMENTALLY (query over the delta + combiner merge,
    timed), and a from-scratch recompute at the same pinned
    generations runs as the oracle (timed — the denominator of
    ``speedup``). ``clients`` reader threads hammer
    ``engine.read_view`` throughout; every read's
    ``(generations, result)`` pair is verified post-hoc against the
    from-scratch oracle at exactly those generations — the
    generation-consistency proof (``oracle_mismatches`` MUST be 0).
    """
    import pandas as pd

    import cylon_tpu as ct
    from cylon_tpu import tpch, views, watchdog
    from cylon_tpu.fallback import _resolve_limit
    from cylon_tpu.serve import ServeEngine
    from cylon_tpu.tpch import dbgen
    from cylon_tpu.tpch.manifest import FALLBACK, MANIFEST

    if delta_sf is None:
        delta_sf = max(sf / 100.0, 1e-4)
    keep = _refresh_keep(mix)
    env = ct.CylonEnv(ct.TPUConfig())
    base = dbgen.generate(sf, seed, keep=keep)
    # resident tables stay LOCAL (host-backed Tables): each RF1 append
    # rebuilds the table host-side, and the eager fallback gathers to
    # host anyway — a per-round mesh re-scatter would only add noise
    # to the walls being compared
    resident = tpch.ingest(base)
    engine = ServeEngine(env)
    for name, df in resident.items():
        engine.register_table(f"tpch/{name}", df)

    query_fns = {q: _mk_view_query(q) for q in mix}
    limits = {}
    for q in mix:
        spec = FALLBACK[q]
        if spec["merge"] == "twophase":
            from cylon_tpu.errors import InvalidArgument

            raise InvalidArgument(
                f"--refresh mix cannot include two-phase query {q!r}: "
                "its view state is a phase-1 partial, which needs a "
                "partial-returning query fn (see tests/test_views.py);"
                f" maintainable here: {REFRESH_MIX}")
        limits[q] = _resolve_limit(getattr(tpch, q), spec, {})
        engine.register_view(
            f"view/{q}", query_fns[q], spec,
            sources={t: f"tpch/{t}" for t in MANIFEST[q]},
            delta_source="lineitem", limit=limits[q])

    # the bench-side delta history: content at ANY generation rebuilds
    # as base + deltas[:gen-1] — what the oracle recomputes from
    host_frames = {t: df.to_pandas() for t, df in resident.items()}
    delta_hist: "dict[str, list]" = {"orders": [], "lineitem": []}
    n_base_ord = int(len(host_frames["orders"]))

    def content_at(tname: str, gen: int):
        hist = delta_hist.get(tname, ())
        parts = [host_frames[tname]] + list(hist[:max(gen - 1, 0)])
        return (parts[0] if len(parts) == 1
                else pd.concat(parts, ignore_index=True))

    oracle_cache: dict = {}
    oracle_mu = threading.Lock()

    def oracle_for(q: str, gens: dict):
        """(result, wall_s, fresh) of the from-scratch recompute at
        exactly ``gens`` — cached per pinned-generation combo."""
        combo = tuple(sorted(gens.items()))
        with oracle_mu:
            hit = oracle_cache.get((q, combo))
        if hit is not None:
            return hit[0], hit[1], False
        # view generations are keyed by query ALIAS (== the TPC-H
        # table name here; the catalog id is tpch/<alias>)
        tabs = {a: content_at(a, g) for a, g in gens.items()}
        t0 = time.perf_counter()
        out = query_fns[q](tabs)
        wall = time.perf_counter() - t0
        res = views.present(out, FALLBACK[q], limits[q])
        with oracle_mu:
            oracle_cache[(q, combo)] = (res, wall)
        return res, wall, True

    refresh_walls = {q: 0.0 for q in mix}
    recompute_walls = {q: 0.0 for q in mix}
    mismatches: list = []
    errors: list = []
    samples: list = []  # (q, generations, result, lag)
    lock = threading.Lock()
    stop_readers = threading.Event()
    refreshes = [0]
    full_recomputes = [0]
    delta_rows_total = [0]

    def reader(i: int):
        while not stop_readers.is_set():
            for q in mix:
                try:
                    r = engine.read_view(f"view/{q}")
                except Exception as e:
                    with lock:
                        errors.append((f"read view/{q}",
                                       f"{type(e).__name__}: {e}"))
                    continue
                with lock:
                    samples.append((q, dict(r["generations"]),
                                    r["result"], int(r["lag"])))
            time.sleep(0.01)

    t0 = time.perf_counter()
    with watchdog.watched_section("serve_request",
                                  detail="refresh_bench"):
        threads = [threading.Thread(target=reader, args=(i,),
                                    name=f"refresh-reader-{i}")
                   for i in range(clients)]
        for th in threads:
            th.start()
        try:
            for r in range(rounds):
                d = dbgen.generate(delta_sf, seed + 1 + r, keep=keep)
                # RF1 key offset: this round's new orders (and their
                # lineitems) land in a key range disjoint from the
                # base AND every other round; dimension keys
                # (custkey/suppkey/partkey) stay inside the base
                # ranges because delta_sf < sf
                n_d = int(len(d["orders"]["o_orderkey"]))
                off = n_base_ord + r * n_d
                d["orders"]["o_orderkey"] = (
                    d["orders"]["o_orderkey"] + off)
                d["lineitem"]["l_orderkey"] = (
                    d["lineitem"]["l_orderkey"] + off)
                for t in ("orders", "lineitem"):
                    engine.append_table(f"tpch/{t}", d[t])
                    delta_hist[t].append(pd.DataFrame(
                        {c: np.asarray(v) for c, v in d[t].items()}))
                delta_rows_total[0] += int(
                    len(d["lineitem"]["l_orderkey"]))
                for q in mix:
                    out = engine.refresh_view(f"view/{q}")
                    refreshes[0] += 1
                    refresh_walls[q] += out["wall_s"]
                    if out["full_recompute"]:
                        full_recomputes[0] += 1
                    want, wall, fresh = oracle_for(
                        q, out["generations"])
                    if fresh:
                        recompute_walls[q] += wall
                    got = engine.read_view(f"view/{q}")
                    if (got["generations"] == out["generations"]
                            and not _results_match(got["result"],
                                                   want)):
                        mismatches.append(
                            (q, dict(out["generations"]),
                             "post-refresh mismatch"))
        finally:
            stop_readers.set()
            for th in threads:
                th.join()
    wall = time.perf_counter() - t0

    # post-hoc audit: EVERY concurrent read must equal the
    # from-scratch recompute at its pinned generations (distinct
    # combos are few — state only changes under refresh — so the
    # oracle cache absorbs the volume)
    lag_max = 0
    for q, gens, result, lag in samples:
        lag_max = max(lag_max, lag)
        want, _, _ = oracle_for(q, gens)
        if not _results_match(result, want):
            mismatches.append((q, gens, "concurrent read mismatch"))
    engine.close(wait=True)

    refresh_wall = sum(refresh_walls.values())
    recompute_wall = sum(recompute_walls.values())
    record = {
        "metric": "refresh_bench_tpch_rf1",
        "sf": sf,
        "delta_sf": delta_sf,
        "rounds": rounds,
        "clients": clients,
        "views": [f"view/{q}" for q in mix],
        # one RF1 round appends to BOTH orders and lineitem
        "appends": rounds * 2,
        "refreshes": refreshes[0],
        "full_recomputes": full_recomputes[0],
        "delta_rows_total": delta_rows_total[0],
        "refresh_wall_s": round(refresh_wall, 4),
        "recompute_wall_s": round(recompute_wall, 4),
        "speedup": (round(recompute_wall / refresh_wall, 2)
                    if refresh_wall > 0 else None),
        "per_view": {q: {
            "refresh_wall_s": round(refresh_walls[q], 4),
            "recompute_wall_s": round(recompute_walls[q], 4),
            "speedup": (round(recompute_walls[q] / refresh_walls[q], 2)
                        if refresh_walls[q] > 0 else None),
        } for q in mix},
        "generation_lag": lag_max,
        "reads_total": len(samples),
        "oracle_mismatches": len(mismatches),
        "mismatch_detail": mismatches[:8],
        "errors": len(errors),
        "error_detail": errors[:8],
        "wall_s": round(wall, 3),
        "view_stats": engine.view_stats(),
    }
    return record


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--requests", type=int, default=2,
                   help="queries per client")
    p.add_argument("--sf", type=float, default=0.002)
    p.add_argument("--schedule", default="roundrobin",
                   choices=("roundrobin", "priority"))
    p.add_argument("--slo", type=float, default=0.0,
                   help="per-request SLO seconds (0 = unbounded)")
    p.add_argument("--max-queue", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mix", default=None,
                   help="comma-separated TPC-H query names (default: "
                        f"{','.join(DEFAULT_MIX)}; --refresh default: "
                        f"{','.join(REFRESH_MIX)})")
    p.add_argument("--slo-target", type=float, default=0.0,
                   help="per-tenant success objective for burn-rate "
                        "accounting (e.g. 0.99; 0 = policy/env default)")
    p.add_argument("--slo-latency", type=float, default=0.0,
                   help="latency objective seconds (0 = success-only)")
    p.add_argument("--storm", type=int, default=0,
                   help="after the replay, drive N fault-storm "
                        "requests on one tenant and record the "
                        "/health ok->unhealthy->ok transitions + "
                        "/events replay (the ISSUE 14 acceptance)")
    p.add_argument("--fleet", action="store_true",
                   help="replicated-fleet mode (ISSUE 15): spawn "
                        "--engines engine PROCESSES over one durable "
                        "tree, route the mix through a FleetRouter, "
                        "SIGKILL one engine mid-run and prove 0 lost "
                        "acks / 0 double-executions across the "
                        "failover")
    p.add_argument("--engines", type=int, default=2,
                   help="engine process count for --fleet (>= 2)")
    p.add_argument("--no-kill", action="store_true",
                   help="--fleet without the mid-run kill (baseline)")
    p.add_argument("--fleet-trace", action="store_true",
                   help="with --fleet (ISSUE 20): arm CYLON_TPU_TRACE "
                        "fleet-wide, stitch the router's and every "
                        "engine's trace segments onto one clock "
                        "(ping-handshake offsets) and write the "
                        "Chrome Trace artifact; the record gains the "
                        "stitched-request report and the query-profile "
                        "cost-model audit")
    p.add_argument("--hot-mix", action="store_true",
                   help="hot-mix dedup mode (ISSUE 19): replay a hot "
                        "mix (identical fingerprints) through the "
                        "FleetRouter against a single uncached "
                        "baseline engine, then against the warmed "
                        "coalescing + versioned-result-cache plane, "
                        "and gate the QPS multiplier at >= 10x with "
                        "0 oracle mismatches and 0 stale results "
                        "across a mid-probe append")
    p.add_argument("--refresh", action="store_true",
                   help="incremental-view mode (ISSUE 18): drive "
                        "TPC-H RF1-style appends interleaved with the "
                        "mix served as materialized views, and gate "
                        "on refresh wall <= 0.5x the from-scratch "
                        "recompute wall with 0 oracle mismatches on "
                        "concurrent generation-pinned reads")
    p.add_argument("--appends", type=int, default=2,
                   help="RF1 append rounds for --refresh")
    p.add_argument("--delta-sf", type=float, default=0.0,
                   help="scale factor of each RF1 delta (0 = sf/100)")
    args = p.parse_args(argv)
    mix_arg = (tuple(q.strip() for q in args.mix.split(",")
                     if q.strip()) if args.mix else None)

    if args.hot_mix:
        record = run_hotmix_bench(
            clients=args.clients, requests=max(args.requests, 2),
            sf=args.sf, seed=args.seed, mix=mix_arg or DEFAULT_MIX,
            engines=args.engines)
        missing = REQUIRED_HOTMIX_FIELDS - record.keys()
        assert not missing, f"hot-mix record dropped fields {missing}"
        _emit_record(record)
        # the acceptance gate: a stale result served across an append,
        # an oracle mismatch, or a dedup plane that is not at least
        # 10x the uncached baseline's QPS is a FAILED bench
        if record["oracle_mismatches"] or record["errors"] \
                or record["stale_results"]:
            return 1
        if record["qps_multiplier"] is None \
                or record["qps_multiplier"] < 10.0:
            return 1
        return 0

    if args.refresh:
        record = run_refresh_bench(
            sf=args.sf,
            delta_sf=args.delta_sf if args.delta_sf > 0 else None,
            rounds=args.appends, clients=args.clients,
            seed=args.seed, mix=mix_arg or REFRESH_MIX)
        missing = REQUIRED_REFRESH_FIELDS - record.keys()
        assert not missing, f"refresh record dropped fields {missing}"
        _emit_record(record)
        # the acceptance gate: a stale or wrong read (oracle mismatch)
        # or an incremental refresh that is not at least 2x cheaper
        # than recomputing from scratch is a FAILED bench
        if record["oracle_mismatches"] or record["errors"]:
            return 1
        if record["speedup"] is None or record["speedup"] < 2.0:
            return 1
        return 0

    if args.fleet:
        from cylon_tpu.serve.fleet import run_fleet_bench

        record = run_fleet_bench(
            clients=args.clients,
            requests=max(args.requests, 2), sf=args.sf,
            seed=args.seed, engines=args.engines,
            mix=mix_arg or DEFAULT_MIX,
            kill_mid_run=not args.no_kill,
            fleet_trace=args.fleet_trace)
        missing = REQUIRED_FLEET_FIELDS - record.keys()
        assert not missing, f"fleet record dropped fields {missing}"
        if args.fleet_trace:
            missing = REQUIRED_FLEET_TRACE_FIELDS - record.keys()
            assert not missing, \
                f"fleet-trace record dropped fields {missing}"
        _emit_record(record)
        # the acceptance gate: an acknowledged request lost, a double
        # execution, an oracle mismatch, or (with the kill armed) a
        # run that never failed over is a FAILED bench
        if record["lost_acks"] or record["double_executions"] \
                or record["oracle_mismatches"] or record["errors"]:
            return 1
        if not args.no_kill and record["failovers"] < 1:
            return 1
        return 0

    if args.storm:
        # the storm acceptance wants the full plane armed: the event
        # journal and the router's HTTP view of /health
        os.environ.setdefault("CYLON_TPU_EVENTS", "1")
        os.environ.setdefault("CYLON_TPU_SERVE_HTTP_PORT", "0")

    record = run_bench(
        clients=args.clients, requests=args.requests, sf=args.sf,
        schedule=args.schedule, slo=args.slo,
        max_queue=args.max_queue, seed=args.seed,
        mix=mix_arg or DEFAULT_MIX,
        slo_target=args.slo_target if args.slo_target > 0 else None,
        slo_latency=args.slo_latency if args.slo_latency > 0 else None,
        storm=args.storm)
    missing = REQUIRED_SERVE_FIELDS - record.keys()
    assert not missing, f"serve record dropped fields {missing}"
    _emit_record(record)
    # a replay that corrupted results or failed requests is a FAILED
    # bench, not a slow one; a storm leg that never drove /health to
    # unhealthy AND back to ok failed its acceptance
    if record["oracle_mismatches"] or record["errors"]:
        return 1
    if args.storm and not record.get("storm", {}).get("recovered"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
