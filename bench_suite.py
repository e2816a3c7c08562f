"""Full benchmark suite: one JSON line per BASELINE.json config.

``bench.py`` stays the driver's single headline line (config 2); this
suite covers all five configs for broader tracking:

1. local inner merge (pycylon ``DataFrame.merge`` analog)
2. distributed hash inner-join (headline; same as bench.py)
3. distributed groupby-aggregate (sum/mean/count)
4. distributed sample-sort + set-union
5. TPC-H wall-clock, all 22 queries

Scale knobs: CYLON_BENCH_ROWS (default 1M), CYLON_BENCH_TPCH_SF
(default 0.1), CYLON_BENCH_REPS (default 3). Distributed configs run
over every visible device (one chip, or N with a mesh).
``--trace`` arms the flight recorder (``CYLON_TPU_TRACE``, inherited
by spawned children) and appends a ``trace_artifact`` record pointing
at the Chrome Trace JSON written next to the records.

The CHAOS section (``--chaos``) is the kill-level robustness proof: for
each out-of-core op it hard-kills a child mid-pass at a seeded fault
point (``FaultRule.kill`` → ``os._exit``), resumes from the durable
checkpoint in a fresh child, and asserts the resumed output is
byte-identical (sha256) to a fault-free oracle child's — one JSON
record per op. See docs/resilience.md "Checkpoint & recovery".

The EXCHANGE section (``--exchange``, also spawned automatically at the
end of a full run) times the multi-device shuffle/dist_join paths on an
8-device virtual CPU mesh — the one place the variable-size all-to-all
(`parallel.shuffle.exchange_arrays`) actually exchanges between shards
on this single-chip machine. Without it a shuffle regression would ship
invisibly behind the world==1 short-circuit (VERDICT r2 weak #2).
Parity: ``cpp/src/examples/bench/table_join_dist_test.cpp:38-56``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np



def _artifacts_dir() -> str:
    """The bench-artifact directory — bench.py's ARTIFACTS_DIR is the
    single source of truth (one env knob, one default literal), so the
    suite's trace paths can never diverge from the headline bench's."""
    import bench as headline

    return headline.ARTIFACTS_DIR
def _timeit(fn, sync, reps):
    fn()  # compile
    float(np.asarray(sync()).ravel()[0])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        float(np.asarray(sync()).ravel()[0])
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _metrics_block():
    """Registry snapshot block embedded in EVERY bench record: byte /
    overflow / retry / padding context rides the perf trajectory, not
    just wall time (the required keys are pinned by
    ``tests/test_bench_guard.py`` so a future PR cannot silently drop
    them). Failure-proof: a bench must never die on telemetry."""
    try:
        from cylon_tpu import telemetry

        return telemetry.bench_metrics()
    except Exception as e:  # pragma: no cover - import-time breakage
        return {"telemetry_error": f"{type(e).__name__}: {e}"}


def _emit_record(line: dict):
    """The ONE stdout sink for bench JSON records — every record gets
    the telemetry ``metrics`` block attached here (the bench guard
    lints that no other call site prints ``json.dumps`` directly)."""
    line = dict(line)
    line["metrics"] = _metrics_block()
    print(json.dumps(line))


def _emit(metric, value, unit, baseline=None):
    line = {"metric": metric, "value": round(value, 1), "unit": unit}
    if baseline:
        line["vs_baseline"] = round(value / baseline, 3)
    _emit_record(line)


def _subproc_timeout():
    """Ceiling (seconds) on every child process the suite spawns —
    CYLON_BENCH_SUBPROC_TIMEOUT, default 3600, <= 0 disables. A child
    that hangs (wedged device, stuck collective) is killed at the
    ceiling and classified as a CRASH, so the respawn paths re-run its
    unattempted queries instead of the whole harness hanging forever
    with no diagnostics."""
    v = float(os.environ.get("CYLON_BENCH_SUBPROC_TIMEOUT", "3600"))
    return v if v > 0 else None


def device_main():
    """--device: sections 1-6 of the suite, the ones that run on the
    device, in one process. TPC-H accounting goes to the parent through
    CYLON_SCALE_SENTINEL (the parent respawns unattempted queries)."""
    import jax

    import cylon_tpu as ct
    from cylon_tpu import Table
    from cylon_tpu.ops.groupby import groupby_aggregate
    from cylon_tpu.ops.join import join
    from cylon_tpu.ops.selection import sort_table
    from cylon_tpu.ops.setops import union

    n = int(os.environ.get("CYLON_BENCH_ROWS", 1_000_000))
    reps = int(os.environ.get("CYLON_BENCH_REPS", 3))
    sf = float(os.environ.get("CYLON_BENCH_TPCH_SF", 0.1))
    rng = np.random.default_rng(7)
    baseline_join = 1e9 / 4.0 / 64  # Cylon 64-rank rows/s/rank

    left = Table.from_pydict({"k": rng.integers(0, n, n).astype(np.int64),
                              "a": rng.normal(size=n)})
    right = Table.from_pydict({"k": rng.integers(0, n, n).astype(np.int64),
                               "b": rng.normal(size=n)})

    # 1. local inner merge ------------------------------------------------
    f1 = jax.jit(lambda l, r: join(l, r, on="k", how="inner",
                                   out_capacity=2 * n))
    out = {}
    t = _timeit(lambda: out.__setitem__("r", f1(left, right)),
                lambda: out["r"].nrows, reps)
    _emit("local_inner_merge_rows_per_sec", n / t, "rows/s", baseline_join)

    # 2. distributed join: bench.py is authoritative; rerun inline -------
    import bench as headline

    headline.main()

    # 3. distributed groupby ---------------------------------------------
    gt = Table.from_pydict({
        "k": rng.integers(0, 10_000, 10 * n).astype(np.int64),
        "v": rng.normal(size=10 * n)})
    f3 = jax.jit(lambda tt: groupby_aggregate(
        tt, ["k"], [("v", "sum"), ("v", "mean"), ("v", "count")],
        out_capacity=16_384))
    t = _timeit(lambda: out.__setitem__("g", f3(gt)),
                lambda: out["g"].nrows, reps)
    _emit("groupby_agg_rows_per_sec", 10 * n / t, "rows/s")

    # 3b. high-cardinality groupby: ~0.6 groups per row — the shape
    # where XLA's segment lowering collapses and the TPU segmented-scan
    # path (kernels.segmented_totals) carries the load
    hk = max(n * 6 // 10, 1)
    ht = Table.from_pydict({
        "k": rng.integers(0, hk, n).astype(np.int64),
        "v": rng.normal(size=n)})
    f3b = jax.jit(lambda tt: groupby_aggregate(
        tt, ["k"], [("v", "sum"), ("v", "mean"), ("v", "count")],
        out_capacity=hk + 1))
    t = _timeit(lambda: out.__setitem__("h", f3b(ht)),
                lambda: out["h"].nrows, reps)
    _emit("groupby_highcard_rows_per_sec", n / t, "rows/s")

    # 4. sort + union ------------------------------------------------------
    st = Table.from_pydict({"k": rng.integers(0, 2**40, n).astype(np.int64)})
    f4 = jax.jit(lambda tt: sort_table(tt, ["k"]))
    t = _timeit(lambda: out.__setitem__("s", f4(st)),
                lambda: out["s"].column("k").data[:1], reps)
    _emit("sort_rows_per_sec", n / t, "rows/s")
    ut = Table.from_pydict({"k": rng.integers(0, n, n).astype(np.int64)})
    f4b = jax.jit(lambda a, b: union(a, b, 2 * n))
    t = _timeit(lambda: out.__setitem__("u", f4b(st, ut)),
                lambda: out["u"].nrows, reps)
    _emit("union_rows_per_sec", 2 * n / t, "rows/s")

    # 5. TPC-H (the full 22-query suite), whole-query compiled -----------
    # each query is ONE XLA program (cylon_tpu.plan): one dispatch + one
    # result fetch, vs the eager chain's ~5-10 host syncs
    from cylon_tpu import tpch
    from cylon_tpu.tpch import dbgen

    acct = _run_tpch(sf, reps)
    _report_tpch(acct)

    # 6. TPU ragged exchange: the flagship lax.ragged_all_to_all path,
    # runtime-proven on the real chip (W=1 mesh still compiles and
    # executes the ragged collective, the 64-bit split and
    # Pallas-under-shard_map on real Mosaic — VERDICT r3 missing #3).
    # A TPC-H device crash killed THIS process's backend — skip with a
    # recorded DNF instead of dying on the first dispatch (section 7
    # runs in its own child either way)
    if jax.devices()[0].platform == "tpu":
        if acct["crashed"]:
            _emit("tpu_exchange_skipped_dead_backend", 1,
                  "device crash earlier in suite")
        else:
            tpu_exchange_main()


def main():
    """The full suite. This parent never touches JAX: one process at a
    time may hold the chip, so the device sections run in a child
    (``--device``), a device crash's unattempted TPC-H queries in fresh
    children (``--tpch``), and the CPU-mesh exchange leg in a child
    pinned to the CPU — each after the previous one has exited."""
    sf = float(os.environ.get("CYLON_BENCH_TPCH_SF", 0.1))
    from cylon_tpu.telemetry import trace as _tr

    rc, part, timed_out = _spawn_sentinel(
        "--device", {"CYLON_BENCH_TRACE_PATH": _child_trace_path("device")}
        if _tr.enabled() else None, trace=_tr.enabled())
    if timed_out or part is None:
        _emit_record({"metric": "device_sections_failure",
                      "detail": f"--device child rc={rc} "
                                f"timed_out={timed_out}"})
    elif part["tpch_skipped"]:
        # a device crash truncated the suite and killed the child's
        # backend: finish the unattempted queries in fresh processes
        crash_log: list = []
        agg = {"tpch_attempted": part["tpch_attempted"],
               "tpch_crashed": part["tpch_crashed"],
               "tpch_ooc": part["tpch_ooc"]}
        _tpch_respawn("--tpch", part["tpch_skipped"], agg, crash_log)
        if agg.get("tpch_skipped"):
            # recorded DNF with NAMES: queries no respawn ever reached
            # (each process already emitted its own ooc_dropped lines
            # for lost out-of-core completions — no re-report here)
            _emit_record({"metric": f"tpch_sf{sf}_never_attempted",
                          "value": len(agg["tpch_skipped"]),
                          "unit": "queries",
                          "queries": agg["tpch_skipped"]})
        for msg in crash_log:
            _emit_record({"metric": "tpch_respawn_failure",
                          "detail": msg})

    # 7. exchange path on the virtual CPU mesh: XLA_FLAGS must be set
    # before jax initialises, and JAX_PLATFORMS keeps the child off
    # the chip
    child_env = dict(os.environ)
    child_env["XLA_FLAGS"] = (child_env.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=8")
    child_env["JAX_PLATFORMS"] = "cpu"
    # tracing parent: the child does the actual exchange dispatches, so
    # it gets --trace and its OWN artifact path (the epilogue runs in
    # the child); without the flag the inherited armed recorder would
    # buffer events nobody exports
    tracing_child = _tr.enabled()
    if tracing_child:
        child_env["CYLON_BENCH_TRACE_PATH"] = _child_trace_path("exchange")
    else:
        child_env.pop("CYLON_TPU_TRACE", None)
    try:
        subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--exchange"]
                       + (["--trace"] if tracing_child else []),
                       env=child_env, check=False,
                       timeout=_subproc_timeout())
    except subprocess.TimeoutExpired:
        # recorded DNF for the leg; the rest of the suite already ran
        _emit("exchange_leg_timeout", 1,
              "child killed at CYLON_BENCH_SUBPROC_TIMEOUT")


def _fallback_parts() -> int:
    """Partition count for the suite's spill completions:
    CYLON_BENCH_FALLBACK_PARTS (clamped >= 1, typos degrade to the
    library default) — unset defers to
    ``cylon_tpu.fallback.default_partitions``."""
    from cylon_tpu.fallback import default_partitions

    v = os.environ.get("CYLON_BENCH_FALLBACK_PARTS")
    if not v:
        return default_partitions()
    try:
        return max(int(v), 1)
    except ValueError:  # a typo'd knob must not DNF the completions
        return default_partitions()


def _fallback_resume_dir(name: str) -> "str | None":
    """``CYLON_BENCH_FALLBACK_DIR/<name>`` when the checkpoint-root
    knob is set (a killed at-scale completion resumes instead of
    restarting); None — no checkpointing — otherwise. The ONE place
    the suite derives fallback resume locations."""
    root = os.environ.get("CYLON_BENCH_FALLBACK_DIR")
    return os.path.join(root, name) if root else None


def _fallback_ok(qname: str) -> bool:
    """Can this query complete out-of-core after an OOM? The two
    hand-written streaming paths (q1/q5) plus every query with a
    usable generic spill plan in ``tpch.manifest.FALLBACK``
    (``cylon_tpu.fallback.supports``)."""
    if qname in ("q1", "q5"):
        return True
    from cylon_tpu.fallback import supports

    return supports(qname)


def _is_oom(e: Exception) -> bool:
    """Device-memory exhaustion at a shape is a RESULT (the single-chip
    ceiling); anything else is a regression and must fail the bench."""
    return (isinstance(e, MemoryError)
            or "RESOURCE_EXHAUSTED" in str(e)
            or "ResourceExhausted" in str(e))


def _is_crash(e: Exception) -> bool:
    """Did the DEVICE WORKER die (vs a clean in-process OOM)? Observed
    at SF10: over-allocation comes back as UNAVAILABLE "worker process
    crashed" — the backend is unusable in this process afterwards, so
    the caller must respawn to continue. Also matches the resilience
    layer's Code.Unavailable (injected preemptions)."""
    s = str(e)
    if "UNAVAILABLE" in s or "worker process crashed" in s:
        return True
    code = getattr(e, "code", None)
    return getattr(code, "name", None) == "Unavailable"


def _hbm_stats(tag: str):
    """Emit device memory headroom (HBM on TPU) — the scale runs track
    how close each config sits to the 16 GB ceiling."""
    import jax

    try:
        st = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        st = {}
    used = st.get("bytes_in_use")
    limit = st.get("bytes_limit")
    if used is not None:
        _emit(f"hbm_in_use_{tag}", used / 2**30, "GiB")
    if used is not None and limit:
        _emit(f"hbm_headroom_{tag}", (limit - used) / 2**30, "GiB")


def _run_tpch(sf, reps, tag_hbm: bool = False, ooc_report=None):
    """Time the (whole-query-compiled) TPC-H suite at scale factor
    ``sf``. CYLON_BENCH_TPCH_QUERIES="q1,q3,q5,q6" restricts the set
    (the SF10 runs time the numeric-heavy subset; full suite at
    SF<=1). Emits regrow events: any query whose capacity ladder
    settled above 1x reports its final scale.

    ``ooc_report``: a list to APPEND OOM'd-query names to instead of
    running their out-of-core fallbacks here — the at-scale driver runs
    them in a separate process, because an execution-time OOM leaves
    the failed run's device buffers unreclaimable in-process on this
    backend (the fallback would start with HBM already full).

    Returns ``{"attempted", "crashed", "skipped", "ooc_pending"}``
    (cross-process aggregation rides the sentinel JSON) — ``skipped``
    is the selected queries a device crash left untried, the exact set a
    respawned process should re-run via CYLON_BENCH_TPCH_QUERIES;
    ``ooc_pending`` is the out-of-core completions still owed (crash
    path with no ``ooc_report`` cannot run them in this process — the
    backend is dead — so they are RETURNED and emitted as
    ``ooc_dropped`` rather than silently lost). A crash also emits
    attempted/crashed/skipped count metrics, so a truncated suite is
    visible in the metrics JSON instead of silently DNF."""
    import numpy as np

    from cylon_tpu import tpch
    from cylon_tpu.tpch import dbgen

    only = os.environ.get("CYLON_BENCH_TPCH_QUERIES")
    valid = {f"q{i}" for i in range(1, 23)}
    only = ({q.strip() for q in only.split(",")} & valid) if only else None
    keep_by_table = None
    if only and os.environ.get("CYLON_BENCH_TPCH_PRUNE_INGEST",
                               "1") != "0":
        # query-subset runs generate AND ingest only the columns those
        # queries reference (the storage-scan projection any engine
        # does) — at SF10 a full lineitem load alone is ~10 GB of HBM,
        # and at SF100 full generation alone would dwarf host RAM.
        # Keep-sets AND predicate are the SAME explicit manifest +
        # queries.manifest_keep that queries._tables prunes by, so the
        # two layers cannot diverge
        from cylon_tpu.tpch.manifest import MANIFEST
        from cylon_tpu.tpch.queries import manifest_keep

        keep_by_table = {}
        for qn in sorted(only):
            for t, ks in MANIFEST[qn].items():
                keep_by_table.setdefault(t, set()).update(ks)
    data = dbgen.generate(sf=sf, seed=0, keep=keep_by_table)
    if keep_by_table is not None:
        from cylon_tpu.tpch.manifest import MANIFEST
        from cylon_tpu.tpch.queries import manifest_keep

        # a table NO selected query reads keeps zero columns (ingest
        # builds an empty frame for it; nothing is device_put)
        data = {t: {c: cols[c] for c in manifest_keep(
                        t, cols, keep_by_table.get(t, frozenset()))}
                for t, cols in data.items()}
    names = [f"q{i}" for i in range(1, 23)]
    selected = [q for q in names if only is None or q in only]
    # EXPLAIN-style pre-flight (the single-chip ceiling, same contract
    # as fallback.run_query's): with a device budget in force
    # (CYLON_TPU_HBM_BUDGET_BYTES or real allocator limits), a query
    # whose manifest-projected input bytes × the transient-expansion
    # knob exceed free device memory routes STRAIGHT to the out-of-
    # core completion — no doomed ingest+dispatch. At SF100 this is
    # load-bearing: the in-core attempt would die on ingest before any
    # recordable OOM. Plain CPU (no budget) stands down as ever.
    from cylon_tpu import fallback as _fb

    free = _fb.free_hbm_bytes()
    preflight: dict = {}
    if free is not None:
        from cylon_tpu.tpch.manifest import MANIFEST

        exp = _fb.expansion_factor()
        for qname in selected:
            est = 0
            for t, ks in MANIFEST[qname].items():
                for c in ks:
                    arr = data.get(t, {}).get(c)
                    if arr is None:
                        continue
                    a = np.asarray(arr)
                    # object strings ride as padded device bytes:
                    # ~64 B/row is the manifest columns' envelope
                    est += (len(a) * 64 if a.dtype == object
                            else a.nbytes)
            est = int(est * exp)
            if est > free:
                preflight[qname] = est
    # tables pre-ingested once (the reference's TPC-H timing also runs
    # on loaded tables); tpch.ingest applies the storage policy
    # (comment columns as device bytes — at SF>=1 a host dictionary
    # for them would be the dataset). When EVERY selected query was
    # preflight-routed there is nothing to ingest — skip the load
    # entirely (at SF100 even the pruned ingest is tens of GB)
    dfs = (tpch.ingest(data)
           if len(preflight) < len(selected) else None)
    if tag_hbm:
        _hbm_stats(f"tpch_sf{sf}_ingest")
    # eager mode: one compiled program PER OPERATOR instead of per
    # query — at very large scale factors the whole-query programs can
    # take minutes each to compile, and the per-op executables are
    # shared across queries
    eager = os.environ.get("CYLON_BENCH_TPCH_MODE") == "eager"
    ooc_pending: list = []
    attempted: list = []
    crashed: list = []
    scalar_q = ("q6", "q14", "q17", "q19")

    def _accounting(pending=()):
        skipped = [q for q in selected if q not in attempted]
        _emit(f"tpch_sf{sf}_attempted", len(attempted), "queries")
        _emit(f"tpch_sf{sf}_crashed", len(crashed), "queries")
        _emit(f"tpch_sf{sf}_skipped", len(skipped), "queries")
        return {"attempted": list(attempted), "crashed": list(crashed),
                "skipped": skipped, "ooc_pending": list(pending)}

    def _checkpoint():
        # per-query progress snapshot to the sentinel: if this process
        # is KILLED mid-query (parent timeout on a hang, OOM-killer),
        # the parent still learns exactly what was attempted and
        # charges the in-flight query as the crash (_classify_timeout)
        sentinel = os.environ.get("CYLON_SCALE_SENTINEL")
        if not sentinel:
            return
        try:
            # tmp + fsync + rename (resilience.atomic_write_json): the
            # parent may KILL this process at any instant (that is the
            # point), and a torn half-written JSON would read as "no
            # report" — losing the whole checkpoint history
            from cylon_tpu.resilience import atomic_write_json

            atomic_write_json(sentinel, {
                "tpch_attempted": list(attempted),
                "tpch_crashed": list(crashed),
                "tpch_skipped": [q for q in selected
                                 if q not in attempted],
                "tpch_ooc": list(ooc_pending)})
        except OSError:
            pass  # checkpointing must never fail the run

    for qname in selected:
        if qname in preflight:
            _emit_record({
                "metric": f"tpch_{qname}_sf{sf}_preflight_spill",
                "value": 1, "unit": "routed to ooc fallback",
                "predicted_bytes": preflight[qname],
                "free_hbm_bytes": free, "path": "ooc_fallback"})
            if _fallback_ok(qname):
                ooc_pending.append(qname)
            else:  # pragma: no cover - all 22 queries carry a plan
                _emit(f"tpch_{qname}_sf{sf}_fallback_unsupported", 1,
                      "no spill decomposition")
            attempted.append(qname)
            _checkpoint()
            continue
        qfn = getattr(tpch, qname) if eager else tpch.compiled(qname)
        res = {}
        try:
            if qname in scalar_q:
                t = _timeit(lambda: res.__setitem__(
                    "r", np.float64(qfn(dfs))), lambda: res["r"], reps)
            else:
                t = _timeit(lambda: res.__setitem__("r", qfn(dfs)),
                            lambda: res["r"].table.nrows, reps)
            # path column: the suite's per-query walls are auditable —
            # in_core here, ooc_fallback on the completion records
            _emit_record({"metric": f"tpch_{qname}_sf{sf}_wall",
                          "value": round(t * 1e3, 1), "unit": "ms",
                          "path": "in_core"})
        except Exception as e:
            if _is_crash(e):
                # the TPU WORKER died (observed at SF10: q1's over-
                # allocation comes back as UNAVAILABLE "worker process
                # crashed", not a clean RESOURCE_EXHAUSTED). The
                # backend is unusable in this process from here on —
                # record it, queue the query's out-of-core completion,
                # and abandon the remaining queries (the driver
                # respawns a fresh process for exactly the skipped
                # set — see _tpch_respawn / scale_main)
                _emit(f"tpch_{qname}_sf{sf}_device_crash", 1,
                      type(e).__name__)
                attempted.append(qname)
                crashed.append(qname)
                if _fallback_ok(qname):
                    ooc_pending.append(qname)
                if ooc_report is not None:
                    ooc_report.extend(ooc_pending)
                else:
                    # no collector and a dead backend: the OOC
                    # completions cannot run in this process — record
                    # the drop (and return it) instead of losing it
                    for q in ooc_pending:
                        _emit(f"tpch_{q}_sf{sf}_ooc_dropped", 1,
                              "device crash; complete via --scale or "
                              "a fresh --tpch run")
                return _accounting(ooc_pending)
            if not _is_oom(e):
                raise
            _emit(f"tpch_{qname}_sf{sf}_oom", 1, type(e).__name__)
            res.clear()
            if _fallback_ok(qname):
                ooc_pending.append(qname)
            else:  # pragma: no cover - all 22 queries carry a plan
                # recorded DNF, never a silent one (since ISSUE 16's
                # two-phase plans this arm is unreachable for TPC-H
                # names; it guards future non-TPC-H query sets)
                _emit(f"tpch_{qname}_sf{sf}_fallback_unsupported", 1,
                      "no spill decomposition")
        attempted.append(qname)
        _checkpoint()
    # regrow events: CompiledQuery memoizes the scale each (query,
    # shape) settled at — >1 means the capacity ladder re-dispatched
    for fn, cq in tpch._COMPILED.items():
        memo = getattr(cq, "_scale_memo", {})
        worst = max(memo.values(), default=1)
        if worst > 1:
            _emit(f"tpch_{fn.__name__}_sf{sf}_regrow_scale", worst, "x")
    if tag_hbm:
        _hbm_stats(f"tpch_sf{sf}_end")
    if ooc_report is not None:
        ooc_report.extend(ooc_pending)
        return _accounting()
    # out-of-core completion for the OOM'd queries (VERDICT r4 missing
    # #2) — AFTER dropping the device-resident ingest (dfs holds e.g.
    # SF10's ~10 GB lineitem; the streaming runs need that HBM back).
    # Slow is fine, DNF is not; its own OOM is a recorded result, not
    # a suite abort.
    if ooc_pending:
        import gc

        from cylon_tpu.tpch import streaming

        dfs = None
        gc.collect()
        _tpch_ooc(data, ooc_pending, sf)
    return _accounting()


def _tpch_ooc(data, qnames, sf):
    """Out-of-core completion for ``qnames``: the hand-written
    streaming variants for q1/q5, the generic manifest-driven
    partition fallback (:mod:`cylon_tpu.fallback`) for every other
    supported query. One wall record per query, ``path=ooc_fallback``
    — with a checkpoint dir (CYLON_BENCH_FALLBACK_DIR) a killed
    at-scale completion resumes instead of restarting."""
    from cylon_tpu import fallback, telemetry
    from cylon_tpu.tpch import streaming

    nparts = _fallback_parts()
    for qname in qnames:
        try:
            # every query here was routed to the spill path by the
            # bench harness after an in-core failure (a clean OOM or a
            # device crash — the sentinel merge loses the distinction,
            # so the label claims neither) — count it on the pinned
            # trajectory counter (run_with_fallback is bypassed here)
            telemetry.counter("ooc.fallbacks", op=qname,
                              reason="bench").inc()
            t0 = time.perf_counter()
            if qname in ("q1", "q5"):
                ofn = (streaming.q1_ooc if qname == "q1"
                       else streaming.q5_ooc)
                out = ofn(data, resume_dir=_fallback_resume_dir(qname))
                out.table.num_rows
            else:
                out = fallback.tpch_fallback(
                    qname, data, n_partitions=nparts,
                    resume_dir=_fallback_resume_dir(qname))
            t = time.perf_counter() - t0
            _emit_record({"metric": f"tpch_{qname}_sf{sf}_ooc_wall",
                          "value": round(t * 1e3, 1), "unit": "ms",
                          "path": "ooc_fallback"})
            del out
        except Exception as e:
            if not _is_oom(e):
                raise
            _emit(f"tpch_{qname}_sf{sf}_ooc_oom", 1, type(e).__name__)


def _child_trace_path(leg: str) -> str:
    """A traced child's own artifact path: sharing the parent's would
    let the parent's end-of-suite artifact overwrite the child's."""
    base = os.environ.get("CYLON_BENCH_TRACE_PATH",
                          os.path.join(_artifacts_dir(),
                                       "bench_suite.trace.json"))
    root = base[:-5] if base.endswith(".json") else base
    return f"{root}.{leg}.json"


def _spawn_sentinel(flag, extra_env=None, trace: bool = False):
    """Run this file in a child process with ``flag``, collecting its
    sentinel-JSON report (the process-boundary contract scale_main's
    docstring explains). Returns ``(returncode, report | None,
    timed_out)`` — a None report means the child died without
    reporting (a crash, not a recorded result); ``timed_out`` means it
    was KILLED at the :func:`_subproc_timeout` ceiling (a hang — the
    report, if any, is the child's last per-query checkpoint, and the
    caller classifies the in-flight query as crashed)."""
    import tempfile

    with tempfile.NamedTemporaryFile("r", suffix=".json",
                                     delete=False) as f:
        sentinel = f.name
    child_env = dict(os.environ)
    child_env.update(extra_env or {})
    child_env["CYLON_SCALE_SENTINEL"] = sentinel
    # untraced sentinel children have no trace exporter wired (their
    # argv has no --trace, so no artifact epilogue runs): an inherited
    # armed recorder would buffer 64k events for nothing — strip it;
    # per-leg tracing is a direct `bench_suite.py --tpch --trace`-style
    # run, or ``trace=True`` (argv gains --trace)
    if not trace:
        child_env.pop("CYLON_TPU_TRACE", None)
    timed_out = False
    try:
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag]
            + (["--trace"] if trace else []),
            env=child_env, timeout=_subproc_timeout()).returncode
    except subprocess.TimeoutExpired:
        rc, timed_out = -9, True  # run() killed the child on expiry
    try:
        with open(sentinel) as f:
            part = json.load(f)
    except (OSError, ValueError):
        part = None
    finally:
        import glob

        # atomic_write_json tmps are '<name>.tmp<pid>_<tid>_<seq>' —
        # a child killed mid-sentinel-write (the chaos legs do exactly
        # that) strands one; sweep the whole family
        for p in [sentinel] + glob.glob(sentinel + ".tmp*"):
            try:
                os.unlink(p)
            except OSError:
                pass
    return rc, part, timed_out


def _classify_timeout(part, queried):
    """A timed-out TPC-H child was killed mid-query: its sentinel (the
    per-query checkpoint ``_run_tpch`` maintains, possibly absent if it
    hung before the first query completed) lists what it finished, so
    the hang victim is the first selected query not yet attempted.
    Classify that query as attempted+crashed — exactly how an in-child
    device crash reports — so ``_tpch_respawn`` strictly shrinks the
    skipped set and re-runs the remainder in a fresh process."""
    part = dict(part or {})
    names = [f"q{i}" for i in range(1, 23)]
    selected = [q for q in names if q in queried]
    attempted = list(part.get("tpch_attempted", []))
    hung = next((q for q in selected if q not in attempted), None)
    if hung is not None:
        attempted.append(hung)
        part["tpch_crashed"] = part.get("tpch_crashed", []) + [hung]
    part["tpch_attempted"] = attempted
    part["tpch_skipped"] = [q for q in selected if q not in attempted]
    part.setdefault("tpch_ooc", [])
    return part


def _tpch_respawn(flag, skipped, agg, crash_log):
    """Crash-respawn driver: a device crash abandons every query after
    it AND leaves the crashed process's backend unusable, so the only
    way to finish the suite is a FRESH process restricted (via
    CYLON_BENCH_TPCH_QUERIES) to exactly the unattempted set. Loops
    until the suite completes, a child dies without reporting, or a
    respawn makes no progress (every child attempts >= 1 query — a
    crashed query counts as attempted — so the skipped set strictly
    shrinks on any healthy child). Children's attempted/crashed/
    ooc-pending lists accumulate into ``agg``; the surviving skipped
    set lands in ``agg["tpch_skipped"]`` — non-empty means recorded
    DNF, never a silent one."""
    prev = None
    while skipped and skipped != prev:
        prev = skipped
        _emit("tpch_respawn_queries", len(skipped), "queries")
        rc, part, timed_out = _spawn_sentinel(flag, {
            "CYLON_BENCH_TPCH_QUERIES": ",".join(sorted(skipped))})
        if timed_out:
            # a HUNG child (killed at the timeout ceiling) is a crash:
            # charge the in-flight query and re-run the remainder
            part = _classify_timeout(part, set(skipped))
            crash_log.append(
                f"tpch respawn ({flag}) timed out; "
                f"{part['tpch_crashed'][-1:]} classified as crashed")
        elif part is None:
            crash_log.append(
                f"tpch respawn ({flag}) exited rc={rc} with no "
                "sentinel")
            break
        for k in ("tpch_attempted", "tpch_crashed", "tpch_ooc"):
            agg[k] = agg.get(k, []) + part.get(k, [])
        skipped = part.get("tpch_skipped", [])
    agg["tpch_skipped"] = skipped
    return agg


def scale_main():
    """--scale: the at-scale proof runs (VERDICT r3 missing #2) on the
    real chip — TPC-H at CYLON_BENCH_TPCH_SF (1 / 10) and the
    BASELINE.json larger join/sort configs at CYLON_BENCH_ROWS
    (10M / 100M), with HBM headroom tracked per stage.

    PROCESS STRUCTURE: each in-core attempt that may exceed HBM runs in
    its OWN child process (``--scale-incore=<join|sort|tpch>``), and the
    out-of-core completions run here in the parent afterwards. An
    execution-time OOM on this backend leaves the failed run's device
    buffers unreclaimable in-process (observed: after the 100M join's
    OOM, a 128 MB device_put still reports RESOURCE_EXHAUSTED after
    releasing every reference + gc), so "record the OOM, then complete
    out-of-core" is only reliable across a process boundary. The child
    reports which configs OOM'd via a sentinel JSON file; metrics print
    straight through to this process's stdout. The chip is leased one
    process at a time — children run sequentially and exit cleanly
    before the parent touches the device."""
    n = int(os.environ.get("CYLON_BENCH_ROWS", 0))
    sf = float(os.environ.get("CYLON_BENCH_TPCH_SF", 0))
    report = {}
    crashed = []
    legs = (["join", "sort"] if n else []) + (["tpch"] if sf else [])
    for leg in legs:
        rc, part, timed_out = _spawn_sentinel(f"--scale-incore={leg}")
        if timed_out and leg == "tpch":
            # hung child killed at the ceiling: classify the in-flight
            # query as crashed (from its per-query checkpoint) and let
            # the respawn path below finish the remainder
            only = os.environ.get("CYLON_BENCH_TPCH_QUERIES")
            queried = ({q.strip() for q in only.split(",")} if only
                       else {f"q{i}" for i in range(1, 23)})
            part = _classify_timeout(part, queried)
            crashed.append(f"--scale-incore={leg} timed out; "
                           "in-flight query classified as crashed")
        elif timed_out:
            crashed.append(f"--scale-incore={leg} killed at "
                           "CYLON_BENCH_SUBPROC_TIMEOUT (hang)")
            continue
        elif part is None:
            # the child died without reporting (not a recorded OOM — a
            # crash). Record it, but DON'T abort yet: earlier legs'
            # out-of-core completions must still run ("slow is fine,
            # DNF is not"), and they cannot run interleaved here — the
            # chip is leased one process at a time, so the parent must
            # not touch the device until every child has exited
            crashed.append(f"--scale-incore={leg} exited rc={rc} "
                           "with no sentinel")
            continue
        report.update(part)
        if leg == "tpch" and part.get("tpch_skipped"):
            # a device crash truncated the suite mid-leg: respawn fresh
            # processes for the unattempted queries (accumulating their
            # attempted/crashed/ooc reports into this parent's view)
            _tpch_respawn(f"--scale-incore={leg}",
                          part["tpch_skipped"], report, crashed)
    if "tpch_attempted" in report:
        _emit(f"tpch_sf{sf}_total_attempted",
              len(report["tpch_attempted"]), "queries")
        _emit(f"tpch_sf{sf}_total_crashed",
              len(report.get("tpch_crashed", [])), "queries")
        _emit(f"tpch_sf{sf}_total_skipped",
              len(report.get("tpch_skipped", [])), "queries")
        if report.get("tpch_skipped"):
            crashed.append("tpch queries never attempted after "
                           f"respawns: {report['tpch_skipped']}")

    if report.get("join_oom"):
        # out-of-core completion (VERDICT r4 missing #2): host-
        # partitioned spill join over the same device kernels, in this
        # so-far-device-idle parent (fresh HBM). Fresh rng(7) per leg:
        # each child seeds its own, so the leading draws reproduce
        # exactly the inputs that child OOM'd on
        from cylon_tpu.outofcore import ooc_join

        rng = np.random.default_rng(7)

        nparts = max(8, n // 12_500_000)
        lsrc = {"k": rng.integers(0, n, n).astype(np.int64),
                "a": rng.normal(size=n)}
        rsrc = {"k": rng.integers(0, n, n).astype(np.int64),
                "b": rng.normal(size=n)}
        # the sink pays the full device->host spill per partition
        # (honest wall) but retains only byte counts — keeping the
        # frames would re-create the memory pressure this path exists
        # to avoid
        spilled_bytes = [0]

        def _spill(df):
            spilled_bytes[0] += int(df.memory_usage(index=False).sum())

        t0 = time.perf_counter()
        total = ooc_join(lsrc, rsrc, on="k", n_partitions=nparts,
                         sink=_spill,
                         resume_dir=_fallback_resume_dir("join"))
        t = time.perf_counter() - t0
        assert total > 0
        _emit(f"local_inner_merge_{n}_ooc_rows_per_sec", n / t,
              "rows/s", 1e9 / 4.0 / 64)
        _emit(f"local_inner_merge_{n}_ooc_out_rows", float(total), "rows")
        _emit(f"local_inner_merge_{n}_ooc_spilled",
              spilled_bytes[0] / 2**30, "GiB")
        lsrc = rsrc = None

    if report.get("sort_oom"):
        # sample-sort completion: range-ordered spills ARE the sorted
        # table (the sink only counts bytes here, like the join's)
        from cylon_tpu.outofcore import ooc_sort

        src = {"k": np.random.default_rng(7)
               .integers(0, 2**40, n).astype(np.int64)}
        sorted_bytes = [0]

        def _ssink(df):
            sorted_bytes[0] += int(df.memory_usage(index=False).sum())

        t0 = time.perf_counter()
        total = ooc_sort(src, "k",
                         n_partitions=max(8, n // 12_500_000),
                         sink=_ssink,
                         resume_dir=_fallback_resume_dir("sort"))
        t = time.perf_counter() - t0
        assert total == n
        _emit(f"sort_{n}_ooc_rows_per_sec", n / t, "rows/s")
        _emit(f"sort_{n}_ooc_spilled", sorted_bytes[0] / 2**30, "GiB")
        src = None

    if report.get("tpch_ooc"):
        from cylon_tpu.tpch import dbgen
        from cylon_tpu.tpch.manifest import MANIFEST
        from cylon_tpu.tpch.queries import manifest_keep

        pending = report["tpch_ooc"]
        # generate AND prune to the pending queries' manifests, like
        # the child's ingest — regenerating SF10 unpruned would hold
        # ~10+ GB of comment strings in host RAM for streaming runs
        # that read only lineitem's numeric columns + the small build
        # tables (at SF100 unpruned generation would not fit at all)
        keep_by_table: dict = {}
        for qn in sorted(set(pending)):
            for t, ks in MANIFEST[qn].items():
                keep_by_table.setdefault(t, set()).update(ks)
        data = dbgen.generate(sf=sf, seed=0, keep=keep_by_table)
        data = {t: {c: cols[c] for c in manifest_keep(
                        t, cols, keep_by_table.get(t, frozenset()))}
                for t, cols in data.items()}
        _tpch_ooc(data, pending, sf)

    if crashed:
        raise RuntimeError("; ".join(crashed))


#: the at-scale race configs (ISSUE 16 / ROADMAP item 1) — the runs
#: the paper's claim is about, as named legs so the guard tests can
#: pin them and a driver can re-run any one by name. Each leg is one
#: ``--scale`` invocation (inheriting scale_main's sentinel +
#: crash-respawn machinery) with this env overlaid. The HBM budget
#: pins the v5e single-chip ceiling so in_core-vs-ooc_fallback routing
#: matches the real chip even on a CPU dev host.
SCALE_LEGS = (
    # the full 22-query suite at SF10: per-query wall + path column
    ("tpch_sf10_full", {"CYLON_BENCH_TPCH_SF": "10",
                        "CYLON_BENCH_ROWS": "0",
                        "CYLON_BENCH_TPCH_QUERIES": "",
                        "CYLON_TPU_HBM_BUDGET_BYTES": "17179869184"}),
    # the 1B-row inner-join config (BASELINE.json's headline scale)
    ("join_1b", {"CYLON_BENCH_ROWS": "1000000000",
                 "CYLON_BENCH_TPCH_SF": "0",
                 "CYLON_TPU_HBM_BUDGET_BYTES": "17179869184"}),
    # SF100 Q3/Q5: manifest-pruned generation (full SF100 dbgen would
    # dwarf host RAM), preflight-routed to the out-of-core paths
    ("tpch_sf100_q3q5", {"CYLON_BENCH_TPCH_SF": "100",
                         "CYLON_BENCH_ROWS": "0",
                         "CYLON_BENCH_TPCH_QUERIES": "q3,q5",
                         "CYLON_TPU_HBM_BUDGET_BYTES": "17179869184"}),
)


def race_main():
    """--race: run the :data:`SCALE_LEGS` at-scale configs end to end,
    one ``--scale`` child per leg (each child gets scale_main's full
    sentinel / timeout-classification / crash-respawn coverage), with
    a wall + rc record per leg. CYLON_BENCH_RACE_LEGS="name1,name2"
    restricts the set. A failed leg is a recorded failure line and the
    remaining legs still run — the race never silently truncates."""
    only = os.environ.get("CYLON_BENCH_RACE_LEGS")
    only = {s.strip() for s in only.split(",")} if only else None
    failures = []
    for name, leg_env in SCALE_LEGS:
        if only is not None and name not in only:
            continue
        child_env = dict(os.environ)
        child_env.update(leg_env)
        t0 = time.perf_counter()
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--scale"],
            env=child_env, check=False).returncode
        _emit_record({"metric": f"race_{name}_wall",
                      "value": round(time.perf_counter() - t0, 1),
                      "unit": "s", "leg": name, "rc": rc})
        if rc != 0:
            failures.append(f"{name}: rc={rc}")
    if failures:
        raise RuntimeError("race legs failed: " + "; ".join(failures))


def scale_incore_main(leg: str):
    """One in-core at-scale attempt (see :func:`scale_main`): emits its
    metrics (or its OOM line) and writes the sentinel JSON telling the
    parent which out-of-core completions to run."""
    import jax

    import cylon_tpu as ct  # noqa: F401  (enables x64 + cache)
    from cylon_tpu import Table
    from cylon_tpu.ops.join import join
    from cylon_tpu.ops.selection import sort_table

    reps = int(os.environ.get("CYLON_BENCH_REPS", 2))
    n = int(os.environ.get("CYLON_BENCH_ROWS", 0))
    sf = float(os.environ.get("CYLON_BENCH_TPCH_SF", 0))
    rng = np.random.default_rng(7)
    out = {}
    report = {}

    if leg == "join":
        # pre-flight (ROADMAP item 1 / the 1B-row config): a join whose
        # predicted working set cannot fit free HBM routes STRAIGHT to
        # the parent's out-of-core completion — no doomed multi-minute
        # ingest+dispatch, no allocator churn. 16 bytes/row/table
        # (int64 key + float64 payload) × the transient-expansion knob.
        from cylon_tpu import fallback as _fb
        from cylon_tpu import telemetry as _tm

        est = int(2 * 16 * n * _fb.expansion_factor())
        free = _fb.free_hbm_bytes()
        if free is not None and est > free:
            _tm.counter("ooc.fallbacks", op="join",
                        reason="preflight").inc()
            _emit_record({
                "metric": f"local_inner_merge_{n}_preflight_spill",
                "value": 1, "unit": "routed to ooc_join",
                "predicted_bytes": est, "free_hbm_bytes": free,
                "path": "ooc_fallback"})
            report["join_oom"] = True
        if not report.get("join_oom"):
            try:
                left = Table.from_pydict(
                    {"k": rng.integers(0, n, n).astype(np.int64),
                     "a": rng.normal(size=n)})
                right = Table.from_pydict(
                    {"k": rng.integers(0, n, n).astype(np.int64),
                     "b": rng.normal(size=n)})
                _hbm_stats(f"join_{n}_ingest")
                f1 = jax.jit(lambda l, r: join(l, r, on="k",
                                               how="inner",
                                               out_capacity=2 * n))
                t = _timeit(lambda: out.__setitem__("r",
                                                    f1(left, right)),
                            lambda: out["r"].nrows, reps)
                _emit(f"local_inner_merge_{n}_rows_per_sec", n / t,
                      "rows/s", 1e9 / 4.0 / 64)
                _hbm_stats(f"join_{n}_end")
                report["join_oom"] = False
            except Exception as e:
                if not _is_oom(e):  # only allocation failures are
                    raise           # results
                _emit(f"local_inner_merge_{n}_oom", 1,
                      type(e).__name__)
                report["join_oom"] = True
    elif leg == "sort":
        try:
            st = Table.from_pydict(
                {"k": rng.integers(0, 2**40, n).astype(np.int64)})
            f2 = jax.jit(lambda tt: sort_table(tt, ["k"]))
            t = _timeit(lambda: out.__setitem__("s", f2(st)),
                        lambda: out["s"].column("k").data[:1], reps)
            _emit(f"sort_{n}_rows_per_sec", n / t, "rows/s")
            _hbm_stats(f"sort_{n}_end")
            report["sort_oom"] = False
        except Exception as e:
            if not _is_oom(e):
                raise
            _emit(f"sort_{n}_oom", 1, type(e).__name__)
            report["sort_oom"] = True
    elif leg == "tpch":
        pending: list = []
        acct = _run_tpch(sf, reps, tag_hbm=True, ooc_report=pending)
        report["tpch_ooc"] = pending
        report["tpch_attempted"] = acct["attempted"]
        report["tpch_crashed"] = acct["crashed"]
        report["tpch_skipped"] = acct["skipped"]
    else:
        raise ValueError(f"unknown --scale-incore leg {leg!r}")

    sentinel = os.environ.get("CYLON_SCALE_SENTINEL")
    if sentinel:
        from cylon_tpu.resilience import atomic_write_json

        atomic_write_json(sentinel, report)


def _report_tpch(acct) -> None:
    """TPC-H accounting to the parent, through CYLON_SCALE_SENTINEL
    when it set one."""
    sentinel = os.environ.get("CYLON_SCALE_SENTINEL")
    if sentinel:
        from cylon_tpu.resilience import atomic_write_json

        atomic_write_json(sentinel, {
            "tpch_attempted": acct["attempted"],
            "tpch_crashed": acct["crashed"],
            "tpch_skipped": acct["skipped"],
            "tpch_ooc": acct["ooc_pending"]})


def tpch_main():
    """--tpch: the TPC-H leg alone, in its own process — the respawn
    target main() uses after a device crash (a fresh process is the
    only way to a working backend). CYLON_BENCH_TPCH_QUERIES restricts
    the set; accounting reports through CYLON_SCALE_SENTINEL when the
    parent set one."""
    import cylon_tpu as ct  # noqa: F401  (enables x64 + cache)

    reps = int(os.environ.get("CYLON_BENCH_REPS", 3))
    sf = float(os.environ.get("CYLON_BENCH_TPCH_SF", 0.1))
    acct = _run_tpch(sf, reps)
    _report_tpch(acct)


def chaos_child_main(op: str):
    """One chaos run of an out-of-core op (see :func:`chaos_main`):
    deterministic inputs, an optional seeded hard-kill plan
    (CYLON_BENCH_CHAOS_KILL="point:nth"), a resume checkpoint dir
    (CYLON_BENCH_CHAOS_DIR; unset = fault-free oracle run), and a
    sentinel report carrying the sha256 of the exact byte stream the
    sink saw — the "byte-identical resumed output" proof is a hash
    equality across child processes."""
    import hashlib

    import cylon_tpu  # noqa: F401  (enables x64 + cache)
    from cylon_tpu import resilience, telemetry
    from cylon_tpu.outofcore import ooc_groupby, ooc_join, ooc_sort

    n = int(os.environ.get("CYLON_BENCH_CHAOS_ROWS", "40000"))
    rdir = os.environ.get("CYLON_BENCH_CHAOS_DIR")
    kill = os.environ.get("CYLON_BENCH_CHAOS_KILL")
    if kill:
        point, nth = kill.rsplit(":", 1)
        resilience.install(resilience.FaultPlan(
            [resilience.FaultRule.kill(point, nth=int(nth))]))
    rng = np.random.default_rng(29)
    h = hashlib.sha256()

    def sink(df):
        # %.17g round-trips float64 exactly: identical frames hash
        # identically, and ANY divergence (dtype, order, value) shows
        h.update(df.to_csv(index=False, float_format="%.17g").encode())

    chunk = n // 7 + 1
    if op == "sort":
        src = {"k": rng.integers(0, 1000, n).astype(np.int64),
               "v": rng.normal(size=n)}
        total = ooc_sort(src, ["k", "v"], n_partitions=6,
                         chunk_rows=chunk, sink=sink, resume_dir=rdir)
    elif op == "join":
        left = {"k": rng.integers(0, n, n).astype(np.int64),
                "a": rng.normal(size=n)}
        right = {"k": rng.integers(0, n, n).astype(np.int64),
                 "b": rng.normal(size=n)}
        total = ooc_join(left, right, on="k", n_partitions=6,
                         chunk_rows=chunk, sink=sink, resume_dir=rdir)
    elif op == "groupby":
        src = {"g": rng.integers(0, 64, n).astype(np.int64),
               "v": rng.normal(size=n)}
        out = ooc_groupby(src, ["g"],
                          [("v", "sum", "s"), ("v", "count", "c")],
                          chunk_rows=chunk, resume_dir=rdir)
        pdf = out.to_pandas().sort_values("g").reset_index(drop=True)
        sink(pdf)
        total = len(pdf)
    else:
        raise ValueError(f"unknown chaos op {op!r}")
    sentinel = os.environ.get("CYLON_SCALE_SENTINEL")
    if sentinel:
        from cylon_tpu.resilience import atomic_write_json

        atomic_write_json(sentinel, {
            "sha256": h.hexdigest(), "rows": int(total),
            "units_resumed": telemetry.total("ooc.units_resumed")})


def chaos_main():
    """--chaos: the kill-level robustness proof (ISSUE 8). For each
    out-of-core op (sort/join/groupby), three child processes:

    1. an ORACLE child computes the fault-free output hash;
    2. a KILLED child runs the same workload with a resume_dir and a
       seeded ``FaultRule.kill`` plan — it must die HARD
       (``os._exit``, status ``KILL_EXIT_CODE``) mid-pass, leaving a
       partial durable checkpoint;
    3. a RESUME child re-invokes with identical args + resume_dir —
       it must actually resume (``units_resumed >= 1``) and its output
       hash must equal the oracle's byte for byte.

    Any deviation (child survived the kill, resumed hash differs,
    nothing resumed) fails the leg; one JSON record per op pins the
    artifact."""
    import shutil
    import tempfile

    from cylon_tpu.resilience import KILL_EXIT_CODE

    kills = {"sort": "spill_write:2", "join": "spill_write:2",
             "groupby": "spill_write:2"}
    failures = []
    for op, kill in kills.items():
        tmp = tempfile.mkdtemp(prefix=f"cylon-chaos-{op}-")
        try:
            rc0, oracle, _ = _spawn_sentinel(f"--chaos-child={op}")
            if oracle is None:
                failures.append(f"{op}: oracle child rc={rc0} with "
                                "no report")
                continue
            rc1, rep1, _ = _spawn_sentinel(
                f"--chaos-child={op}",
                {"CYLON_BENCH_CHAOS_DIR": tmp,
                 "CYLON_BENCH_CHAOS_KILL": kill})
            killed = rc1 == KILL_EXIT_CODE and rep1 is None
            if not killed:
                failures.append(
                    f"{op}: kill child exited rc={rc1} "
                    f"(want {KILL_EXIT_CODE}, no sentinel)")
            rc2, rep2, _ = _spawn_sentinel(
                f"--chaos-child={op}", {"CYLON_BENCH_CHAOS_DIR": tmp})
            identical = (rep2 is not None
                         and rep2["sha256"] == oracle["sha256"]
                         and rep2["rows"] == oracle["rows"])
            resumed = bool(rep2) and rep2.get("units_resumed", 0) >= 1
            if not identical:
                failures.append(f"{op}: resumed output != fault-free "
                                f"oracle ({rep2} vs {oracle})")
            elif not resumed:
                failures.append(f"{op}: resume child recomputed from "
                                "scratch (units_resumed=0) — the "
                                "checkpoint was not used")
            _emit_record({
                "metric": f"chaos_{op}_resume",
                "value": 1.0 if (killed and identical and resumed)
                else 0.0,
                "unit": "byte-identical resume",
                "kill": kill,
                "killed_rc": rc1,
                "rows": oracle["rows"],
                "oracle_sha256": oracle["sha256"],
                "resumed_sha256": rep2["sha256"] if rep2 else None,
                "units_resumed": rep2.get("units_resumed") if rep2
                else None,
            })
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    if failures:
        raise RuntimeError("chaos harness failures: "
                           + "; ".join(failures))


def tpu_exchange_main():
    """Force the ragged exchange on a 1-device TPU mesh. Every CPU test
    runs the padded path (XLA:CPU has no ragged-all-to-all thunk) and
    every real-chip op short-circuits at world==1, so without this the
    single most load-bearing TPU component (SURVEY §3.2) would only
    ever be compile-checked. Parity role: the reference's exchange runs
    under every mpirun test (cpp/test/CMakeLists.txt:44-50)."""
    import jax

    import cylon_tpu as ct
    from cylon_tpu import Table
    from cylon_tpu.parallel import dist_join, dtable, shuffle

    n = int(os.environ.get("CYLON_BENCH_EXCHANGE_ROWS", 500_000))
    reps = int(os.environ.get("CYLON_BENCH_REPS", 3))
    rng = np.random.default_rng(13)
    saved = {k: os.environ.get(k)
             for k in ("CYLON_TPU_SHUFFLE", "CYLON_TPU_FORCE_DIST")}
    os.environ["CYLON_TPU_SHUFFLE"] = "ragged"
    os.environ["CYLON_TPU_FORCE_DIST"] = "1"
    try:
        env = ct.CylonEnv(ct.TPUConfig(n_devices=1))
        comments = np.array([f"comment text number {i % 97} row {i}"
                             for i in range(n)], object)
        t_in = Table.from_pydict({
            "k": rng.integers(0, n, n).astype(np.int64),
            "v": rng.normal(size=n),
            "s": comments}, string_storage="bytes")
        out = {}

        def sync():
            return dtable.host_counts(out["r"]).sum()

        t = _timeit(lambda: out.__setitem__(
            "r", shuffle(env, t_in, ["k"])), sync, reps)
        _emit("shuffle_ragged_w1_tpu_rows_per_sec", n / t, "rows/s")

        lt = Table.from_pydict({
            "k": rng.integers(0, n, n).astype(np.int64),
            "a": rng.normal(size=n)})
        rt = Table.from_pydict({
            "k": rng.integers(0, n, n).astype(np.int64),
            "b": rng.normal(size=n)})
        t = _timeit(lambda: out.__setitem__(
            "r", dist_join(env, lt, rt, on="k", how="inner")), sync, reps)
        _emit("dist_join_ragged_w1_tpu_rows_per_sec", n / t, "rows/s")
    finally:
        for k, v in saved.items():  # restore any user-set override
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def weak_scaling_main():
    """--weak-scaling: the headline distributed inner join at
    W=1/2/4/8 on the virtual CPU mesh (rows scale WITH W — n per
    worker held constant), plus the 2x4 hierarchical (slice x worker)
    mesh (VERDICT r4 next #5). Emits one line per world size with
    wall, rows/s, and parallel efficiency vs W=1 — the harness that
    produces the multi-chip scaling claim the moment hardware exists.
    Parity: ``cpp/src/experiments/run_dist_scaling.py:35-36`` (the
    reference's weak-scaling driver). CPU-mesh numbers track SCALING
    SHAPE (collective/kernel overhead growth), not chip throughput."""
    import jax

    jax.config.update("jax_platforms", "cpu")  # a virtual CPU mesh leg
    import cylon_tpu as ct
    from cylon_tpu import Table
    from cylon_tpu.parallel import dist_join, dtable, scatter_table

    from cylon_tpu import telemetry

    n_per = int(os.environ.get("CYLON_BENCH_WEAK_ROWS", 250_000))
    reps = int(os.environ.get("CYLON_BENCH_REPS", 3))
    rng = np.random.default_rng(23)
    out = {}

    def sync():
        return dtable.host_counts(out["r"]).sum()

    def one(env, tag, w):
        n = n_per * w
        lt = scatter_table(env, Table.from_pydict({
            "k": rng.integers(0, n, n).astype(np.int64),
            "a": rng.normal(size=n)}))
        rt = scatter_table(env, Table.from_pydict({
            "k": rng.integers(0, n, n).astype(np.int64),
            "b": rng.normal(size=n)}))
        bytes0 = telemetry.total("exchange.bytes_true")
        calls0 = telemetry.total("exchange.calls")
        t = _timeit(lambda: out.__setitem__(
            "r", dist_join(env, lt, rt, on="k", how="inner")), sync, reps)
        _emit(f"weak_scaling_{tag}_wall_ms", t * 1e3, "ms")
        _emit(f"weak_scaling_{tag}_rows_per_sec", n / t, "rows/s")
        # roofline-honest exchange pricing (VERDICT r5): true payload
        # bytes per dispatch (from the exchange.bytes_true counter the
        # eager dist ops maintain) over the best wall. On the virtual
        # CPU mesh the fraction-of-peak is a SHAPE metric (this host
        # is not a v5e); on real chips the same fields are the
        # roofline position. W=1 short-circuits the exchange entirely
        # (local join path) so no exchange fields are emitted there.
        calls = telemetry.total("exchange.calls") - calls0
        xbytes = telemetry.total("exchange.bytes_true") - bytes0
        if calls:
            bps = (xbytes / calls) / t
            _emit(f"weak_scaling_{tag}_exchange_bytes_per_sec", bps,
                  "bytes/s")
            dev = jax.devices()[0]
            if dev.platform == "tpu":   # a CPU mesh has no roofline
                _emit_record({
                    "metric": f"weak_scaling_{tag}_fraction_of_hbm_peak",
                    "value": round(telemetry.fraction_of_peak(
                        bps, dev.device_kind), 8),
                    "unit": f"of {dev.device_kind} HBM peak"})
            hr = telemetry.metric("exchange.headroom_ratio",
                                  op="dist_join")
            if hr is not None:
                _emit(f"weak_scaling_{tag}_headroom_ratio",
                      float(hr.value), "x (alloc/true rows)")
        out.clear()
        return (n / t) / w          # per-worker throughput

    # On real hardware each worker is a chip and per-worker throughput
    # is the efficiency claim. On the virtual CPU mesh all W "devices"
    # timeshare this host's cores, so the per-worker ratio is bounded
    # by cores/W — the core-normalized number (x W/cores when W>cores)
    # is the scaling-SHAPE metric there (collective+kernel overhead
    # growth with W, what a real mesh would add on top of its chips).
    ncores = os.cpu_count() or 1
    per_worker = {}
    for w in (1, 2, 4, 8):
        if w > len(jax.devices()):
            break
        env = ct.CylonEnv(ct.TPUConfig(n_devices=w))
        per_worker[w] = one(env, f"w{w}", w)
    for w, pw in per_worker.items():
        _emit(f"weak_scaling_w{w}_efficiency_pct",
              100.0 * pw / per_worker[1], "%")
        _emit(f"weak_scaling_w{w}_core_norm_efficiency_pct",
              100.0 * pw * max(1.0, w / max(ncores, 1)) / per_worker[1],
              "%")
    if len(jax.devices()) >= 8:
        # the DCN-analog two-stage exchange on a 2x4 hierarchy
        env = ct.CylonEnv(ct.TPUConfig(devices_per_slice=4))
        pw = one(env, "hier2x4", 8)
        _emit("weak_scaling_hier2x4_efficiency_pct",
              100.0 * pw / per_worker[1], "%")
        _emit("weak_scaling_hier2x4_core_norm_efficiency_pct",
              100.0 * pw * max(1.0, 8 / max(ncores, 1)) / per_worker[1],
              "%")


def exchange_main():
    """Shuffle/dist_join at world 8 on the virtual CPU mesh (see module
    docstring). Numbers are CPU-mesh regression trackers, not TPU
    throughput — compare across commits, not against the chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")  # a virtual CPU mesh leg
    import cylon_tpu as ct
    from cylon_tpu import Table
    from cylon_tpu.parallel import (dist_join, dist_to_pandas, dtable,
                                    scatter_table, shuffle)

    n = int(os.environ.get("CYLON_BENCH_EXCHANGE_ROWS", 500_000))
    reps = int(os.environ.get("CYLON_BENCH_REPS", 3))
    rng = np.random.default_rng(11)
    env = ct.CylonEnv()
    w = env.world_size

    t_in = scatter_table(env, Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "v": rng.normal(size=n)}))
    out = {}

    def sync():
        return dtable.host_counts(out["r"]).sum()

    t = _timeit(lambda: out.__setitem__("r", shuffle(env, t_in, ["k"])),
                sync, reps)
    _emit(f"shuffle_w{w}_cpu_rows_per_sec", n / t, "rows/s")

    lt = scatter_table(env, Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "a": rng.normal(size=n)}))
    rt = scatter_table(env, Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "b": rng.normal(size=n)}))
    t = _timeit(lambda: out.__setitem__(
        "r", dist_join(env, lt, rt, on="k", how="inner")), sync, reps)
    _emit(f"dist_join_w{w}_cpu_rows_per_sec", n / t, "rows/s")

    # bytes string-key join: the device-bytes column exchange + word-wise
    # key compare path (no host dictionary anywhere)
    sn = n // 5
    skeys = np.array([f"key_{i:08d}" for i in
                      rng.integers(0, sn, sn)], object)
    slt = scatter_table(env, Table.from_pydict(
        {"k": skeys, "a": rng.normal(size=sn)}, string_storage="bytes"))
    srt = scatter_table(env, Table.from_pydict(
        {"k": skeys[rng.integers(0, sn, sn)], "b": rng.normal(size=sn)},
        string_storage="bytes"))
    t = _timeit(lambda: out.__setitem__(
        "r", dist_join(env, slt, srt, on="k", how="inner")), sync, reps)
    _emit(f"dist_join_strkey_w{w}_cpu_rows_per_sec", sn / t, "rows/s")

    # distributed TPC-H regression walls (VERDICT r3 weak #3): q3/q5 at
    # SF0.01 on the 8-device mesh — the flagship distributed workload
    # gets a tracked wall, not just a parity test. Parity:
    # cpp/src/examples/bench/table_join_dist_test.cpp:38-56.
    from cylon_tpu import tpch

    data = tpch.generate(sf=0.01, seed=0)
    dfs = tpch.ingest(data)
    for qname in ("q3", "q5"):
        qfn = getattr(tpch, qname)
        res = {}
        t = _timeit(
            lambda: res.__setitem__("r", qfn(dfs, env=env)),
            lambda: dtable.host_counts(res["r"].table).sum(), reps)
        _emit(f"tpch_{qname}_dist_w{w}_sf0.01_wall", t * 1e3, "ms")


def _trace_artifact_record():
    """--trace epilogue: flush the armed flight recorder into a Chrome
    Trace artifact next to the records and pin its path + event count
    in one JSON record (the suite analog of ``bench.py --trace``).
    This artifact is the PARENT process's timeline; the exchange leg
    and the weak-scaling respawn get ``--trace`` forwarded and write
    their own artifacts (distinct paths), while the TPC-H sentinel
    children run with the recorder stripped — recording without an
    exporter would be pure overhead."""
    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import trace

    evts = trace.events()
    path = os.environ.get("CYLON_BENCH_TRACE_PATH",
                          os.path.join(_artifacts_dir(),
                                       "bench_suite.trace.json"))
    telemetry.write_chrome_trace(path, trace.rank_buffers())
    _emit_record({"metric": "trace_artifact", "value": len(evts),
                  "unit": "events",
                  "trace_path": os.path.abspath(path),
                  "trace_events": len(evts),
                  "trace_dropped": trace.dropped()})


if __name__ == "__main__":
    _tracing = "--trace" in sys.argv
    if _tracing and os.environ.get("CYLON_TPU_TRACE", "") in (
            "", "0", "off"):
        # force-arm: an inherited =0/off must not defeat the flag
        os.environ["CYLON_TPU_TRACE"] = "1"
    if "--exchange" in sys.argv:
        exchange_main()
    elif any(a.startswith("--chaos-child=") for a in sys.argv):
        _op = next(a for a in sys.argv
                   if a.startswith("--chaos-child=")).split("=", 1)[1]
        chaos_child_main(_op)
    elif "--chaos" in sys.argv:
        chaos_main()
    elif any(a.startswith("--scale-incore=") for a in sys.argv):
        leg = next(a for a in sys.argv
                   if a.startswith("--scale-incore=")).split("=", 1)[1]
        scale_incore_main(leg)
    elif "--race" in sys.argv:
        race_main()
    elif "--scale" in sys.argv:
        scale_main()
    elif "--tpch" in sys.argv:
        tpch_main()
    elif "--device" in sys.argv:
        device_main()
    elif "--weak-scaling" in sys.argv:
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            # the virtual mesh must exist BEFORE jax initialises; a
            # direct invocation respawns itself with the flag (same
            # pattern as main()'s --exchange leg)
            child_env = dict(os.environ)
            child_env["XLA_FLAGS"] = (
                child_env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
            try:
                # forward --trace so the child (which does the actual
                # work and then runs the artifact epilogue itself)
                # records; the parent exits via sys.exit right here
                sys.exit(subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--weak-scaling"]
                    + (["--trace"] if _tracing else []),
                    env=child_env,
                    timeout=_subproc_timeout()).returncode)
            except subprocess.TimeoutExpired:
                _emit("weak_scaling_timeout", 1,
                      "child killed at CYLON_BENCH_SUBPROC_TIMEOUT")
                sys.exit(124)
        weak_scaling_main()
    else:
        main()
    if _tracing:
        _trace_artifact_record()
