"""Lint-ish guard for the benchmark drivers.

``bench_suite.py`` only executes on real hardware runs, so an undefined
name (the round-5 NameError: ``_is_crash``/``attempted``/``crashed``
referenced but never defined) ships invisibly past the CPU test tier
and detonates mid-benchmark, masking the real device error. This guard
compiles the drivers AND walks their ASTs with a pyflakes-style
scope-aware undefined-name check, so that class of bug fails tier-1.
"""

import ast
import builtins
import pathlib
import py_compile

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DRIVERS = ["bench_suite.py", "bench.py", "cylon_tpu/serve/bench.py",
           "cylon_tpu/serve/fleet.py"]

_FN = (ast.FunctionDef, ast.AsyncFunctionDef)


def _add_arg_names(args: ast.arguments, names: set):
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        names.add(a.arg)
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)


def _bound_names(node, names: set):
    """Names BOUND directly in ``node``'s scope: assignments (incl.
    walrus, aug/ann, for/with/except targets, comprehension targets —
    over-approximated into the enclosing scope), imports, and nested
    def/class names. Does not descend into nested function bodies
    (their locals are invisible here)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, _FN + (ast.ClassDef,)):
            names.add(child.name)
            continue  # nested scope: its bindings are not ours
        if isinstance(child, ast.Lambda):
            continue
        if isinstance(child, ast.Name) and isinstance(
                child.ctx, (ast.Store, ast.Del)):
            names.add(child.id)
        elif isinstance(child, (ast.Import, ast.ImportFrom)):
            for alias in child.names:
                if alias.name == "*":
                    continue
                names.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(child, ast.ExceptHandler) and child.name:
            names.add(child.name)
        elif isinstance(child, (ast.Global, ast.Nonlocal)):
            names.update(child.names)
        _bound_names(child, names)


def _check_scope(node, visible: set, problems: list):
    """Walk loads in ``node``'s scope; recurse into nested functions
    with their own locals layered on top of ``visible``."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, _FN):
            sub = set(visible)
            _add_arg_names(child.args, sub)
            _bound_names(child, sub)
            for dec in child.decorator_list:
                _check_scope(dec, visible, problems)
            _check_scope(child, sub, problems)
            continue
        if isinstance(child, ast.Lambda):
            sub = set(visible)
            _add_arg_names(child.args, sub)
            _bound_names(child, sub)
            _check_scope(child, sub, problems)
            continue
        if isinstance(child, ast.ClassDef):
            # class bodies are rare in drivers; check them as a plain
            # nested view of the enclosing scope
            sub = set(visible)
            _bound_names(child, sub)
            _check_scope(child, sub, problems)
            continue
        if isinstance(child, ast.Name) and isinstance(child.ctx, ast.Load):
            if child.id not in visible:
                problems.append((child.lineno, child.id))
        _check_scope(child, visible, problems)


def undefined_names(path: pathlib.Path) -> list:
    tree = ast.parse(path.read_text(), filename=str(path))
    module_scope = set(dir(builtins)) | {
        "__file__", "__name__", "__doc__", "__package__", "__spec__"}
    _bound_names(tree, module_scope)
    problems: list = []
    _check_scope(tree, module_scope, problems)
    return sorted(set(problems))


@pytest.mark.parametrize("driver", DRIVERS)
def test_driver_compiles(driver):
    py_compile.compile(str(REPO / driver), doraise=True)


@pytest.mark.parametrize("driver", DRIVERS)
def test_driver_has_no_undefined_names(driver):
    bad = undefined_names(REPO / driver)
    assert not bad, (
        f"{driver} references undefined names (the class of bug that "
        f"shipped the _run_tpch NameError): {bad}")


def test_checker_catches_the_original_bug(tmp_path):
    """Self-test: the exact round-5 failure shape — a name used in a
    function that is defined nowhere — is flagged."""
    p = tmp_path / "buggy.py"
    p.write_text(
        "def _run(sf):\n"
        "    try:\n"
        "        pass\n"
        "    except Exception as e:\n"
        "        if _is_crash(e):\n"
        "            attempted.append(sf)\n"
    )
    bad = undefined_names(p)
    assert {n for _, n in bad} == {"_is_crash", "attempted"}


# -------------------------------------------- telemetry record schema
def _json_record_prints(path: pathlib.Path) -> list:
    """(lineno, enclosing function) of every ``print(json.dumps(...))``
    in ``path`` — the shape of a bench JSON record hitting stdout."""
    tree = ast.parse(path.read_text(), filename=str(path))
    hits = []

    def walk(node, fn_name):
        for child in ast.iter_child_nodes(node):
            name = child.name if isinstance(child, _FN) else fn_name
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Name)
                    and child.func.id == "print"
                    and child.args
                    and isinstance(child.args[0], ast.Call)
                    and isinstance(child.args[0].func, ast.Attribute)
                    and child.args[0].func.attr == "dumps"
                    and isinstance(child.args[0].func.value, ast.Name)
                    and child.args[0].func.value.id == "json"):
                hits.append((child.lineno, fn_name))
            walk(child, name)

    walk(tree, "<module>")
    return hits


@pytest.mark.parametrize("driver", DRIVERS)
def test_all_json_records_route_through_emit_record(driver):
    """Every bench JSON record must flow through ``_emit_record`` (the
    one place the telemetry ``metrics`` block is attached) — a direct
    ``print(json.dumps(...))`` elsewhere would ship records without
    byte/overflow/retry context, silently dropping telemetry from the
    perf trajectory."""
    bad = [(ln, fn) for ln, fn in
           _json_record_prints(REPO / driver) if fn != "_emit_record"]
    assert not bad, (
        f"{driver} prints JSON records outside _emit_record at {bad}; "
        "route them through _emit_record so the metrics block rides "
        "along")


def test_emit_record_schema_carries_required_metrics(capsys):
    """Schema check: a record emitted by bench_suite carries a
    ``metrics`` block with every REQUIRED_BENCH_KEYS counter (0 when
    the metric never fired), strict-JSON round-trippable."""
    import json

    import bench_suite
    from cylon_tpu.telemetry import REQUIRED_BENCH_KEYS

    bench_suite._emit("guard_probe", 1.0, "unit")
    out = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(out)
    assert rec["metric"] == "guard_probe"
    assert isinstance(rec.get("metrics"), dict), rec
    missing = [k for k in REQUIRED_BENCH_KEYS if k not in rec["metrics"]]
    assert not missing, f"metrics block missing required keys {missing}"
    # strict JSON end to end: no Infinity/NaN survives export
    json.loads(json.dumps(rec["metrics"], allow_nan=False))


def test_bench_headline_record_carries_metrics(capsys):
    """bench.py's one-line headline record gets the same block."""
    import json

    import bench

    bench._emit_record({"metric": "probe", "value": 1})
    rec = json.loads(capsys.readouterr().out.strip())
    assert "metrics" in rec and isinstance(rec["metrics"], dict)


def test_required_bench_keys_pin_tight_capacity_counters():
    """ISSUE 4 satellite: the tight-exchange counters are part of the
    pinned schema — a future PR cannot drop them from the trajectory."""
    from cylon_tpu.telemetry import REQUIRED_BENCH_KEYS

    assert {"exchange.tight_dispatches",
            "exchange.fallback_regrows"} <= set(REQUIRED_BENCH_KEYS)


def test_headline_schema_pins_roofline_fields():
    """bench.py's headline record must keep the bytes/s +
    fraction-of-peak roofline columns (main() asserts the set before
    emitting, so this pin is enforced at bench runtime too)."""
    import bench

    assert {"exchange_bytes_per_sec",
            "fraction_of_hbm_peak",
            "exchange_note"} <= bench.REQUIRED_HEADLINE_FIELDS


def test_bench_metrics_carries_headroom_gauge():
    """The worst exchange.headroom_ratio across series rides the
    metrics block exactly like pad_ratio (and non-finite values are
    dropped, never exported)."""
    from cylon_tpu import telemetry
    from cylon_tpu.telemetry import bench_metrics

    telemetry.reset("exchange.headroom_ratio")
    assert "exchange.headroom_ratio" not in bench_metrics()
    telemetry.gauge("exchange.headroom_ratio", op="a").set(1.25)
    telemetry.gauge("exchange.headroom_ratio", op="b").set(float("nan"))
    assert bench_metrics()["exchange.headroom_ratio"] == 1.25
    telemetry.reset("exchange.headroom_ratio")


# ------------------------------------------- flight-recorder guards
def _public_dist_ops(tree: ast.Module) -> list:
    """Module-level public dist-op defs in dist_ops.py: the exchange
    drivers and their colocated/local variants — the surface that must
    run under a named span so the flight recorder sees every op."""
    out = []
    for node in ast.iter_child_nodes(tree):
        if isinstance(node, _FN) and not node.name.startswith("_") \
                and (node.name.startswith(("dist_", "colocated_"))
                     or node.name in ("shuffle", "repartition")):
            out.append(node)
    return out


def _has_traced_decorator(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = (target.attr if isinstance(target, ast.Attribute)
                else getattr(target, "id", None))
        if name == "traced":
            return True
    return False


def test_every_public_dist_op_runs_under_a_named_span():
    """ISSUE 5 satellite: every public dist op in parallel/dist_ops.py
    must carry @traced — a new op added without it would silently skip
    the flight recorder (and the span histograms), making its traces
    invisible exactly when someone goes looking for a straggler."""
    path = REPO / "cylon_tpu" / "parallel" / "dist_ops.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    ops = _public_dist_ops(tree)
    assert len(ops) >= 10, "dist-op surface unexpectedly small"
    bare = [f.name for f in ops if not _has_traced_decorator(f)]
    assert not bare, (
        f"public dist ops without @traced spans: {bare} — the flight "
        "recorder (and tracing.timings) cannot see them")


def test_bench_trace_record_schema_pinned():
    """bench.py --trace must pin the artifact path + event count (and
    the rank-track / stage-coverage audit fields) into the headline
    record; main() asserts the set before emitting."""
    import bench

    assert {"trace_path", "trace_events", "trace_rank_tracks",
            "trace_stage_coverage"} <= bench.REQUIRED_TRACE_FIELDS


def test_chrome_trace_exporter_strict_json(monkeypatch):
    """The exporter's output must be strict JSON with monotone ts and
    balanced B/E nesting even when fed non-finite args (the full
    Perfetto-schema walk lives in tests/test_trace_timeline.py)."""
    import json as _json

    from cylon_tpu import telemetry

    bufs = [{"rank": 0, "clock_offset": 0.0, "events": [
        {"kind": "begin", "name": "op", "ts": 1.0, "tid": 1, "id": 1,
         "parent": None, "cat": None, "args": {"bad": float("nan")}},
        {"kind": "end", "name": "op", "ts": 2.0, "tid": 1, "id": 1},
        {"kind": "complete", "name": "exchange", "ts": 1.2, "dur": 0.5,
         "tid": 1, "cat": "stage", "args": {"inf": float("inf")}},
    ]}]
    text = telemetry.chrome_trace_json(bufs)

    def _no_const(_):
        raise AssertionError("non-finite constant in chrome trace")

    doc = _json.loads(text, parse_constant=_no_const)
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    assert sum(1 for e in body if e["ph"] == "B") == \
        sum(1 for e in body if e["ph"] == "E")


# ------------------------------------------------- serve-layer guards
def test_serve_record_schema_pinned():
    """ISSUE 7 satellite: the serve bench record must keep the latency
    quantiles, throughput, cache-hit and rejection columns — the
    serving trajectory is unreadable without them (main() asserts the
    set before emitting, so the pin is enforced at bench runtime too)."""
    from cylon_tpu.serve.bench import REQUIRED_SERVE_FIELDS

    assert {"p50_s", "p99_s", "qps", "cache_hit_rate", "rejected",
            "tenants", "oracle_mismatches"} <= REQUIRED_SERVE_FIELDS


def _watchdog_section_constants(path: pathlib.Path) -> set:
    """String constants passed as the section argument to
    ``watched_section(...)`` / ``bounded(fn, ...)`` / ``check(...)``
    calls anywhere in ``path``."""
    tree = ast.parse(path.read_text(), filename=str(path))
    out = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = (node.func.attr if isinstance(node.func, ast.Attribute)
                 else getattr(node.func, "id", None))
        if fname not in ("watched_section", "bounded", "check"):
            continue
        pos = 1 if fname == "bounded" else 0
        args = node.args
        if len(args) > pos and isinstance(args[pos], ast.Constant) \
                and isinstance(args[pos].value, str):
            out.add(args[pos].value)
    return out


def test_every_serve_entrypoint_runs_under_a_named_watchdog_section():
    """ISSUE 7 satellite: the serve layer's execution paths — the
    scheduler's step runner (service.py) and the bench replayer — must
    run under a NAMED watchdog section, and every section name they use
    must be registered in ``watchdog.SECTIONS`` (an unknown name would
    raise InvalidArgument at runtime; a missing section would mean a
    hung request stalls the engine with zero diagnostics)."""
    from cylon_tpu import watchdog

    for rel in ("cylon_tpu/serve/service.py", "cylon_tpu/serve/bench.py"):
        secs = _watchdog_section_constants(REPO / rel)
        assert secs, f"{rel} never enters a named watchdog section"
        unknown = secs - set(watchdog.SECTIONS)
        assert not unknown, f"{rel} uses unregistered sections {unknown}"
        assert "serve_request" in secs, (
            f"{rel} must run its serve work under the serve_request "
            "section")


def test_serve_request_section_registered_not_retryable():
    """The serve_request section exists in BOTH registries (watchdog
    retryability + config budget defaults — the import-time assertion
    requires them to match) and is never engine-retryable."""
    from cylon_tpu import watchdog
    from cylon_tpu.config import DEADLINE_SECTIONS

    assert watchdog.SECTIONS.get("serve_request") is False
    assert "serve_request" in DEADLINE_SECTIONS


def test_serve_record_schema_pins_robustness_columns():
    """ISSUE 8 satellite: the shed/journal/recovery counters are part
    of the pinned serve-record schema — a chaos run's load sheds and
    journal replays ride the serving trajectory, and a refactor cannot
    silently drop them."""
    from cylon_tpu.serve.bench import REQUIRED_SERVE_FIELDS

    assert {"shed", "journal_replayed",
            "recoveries"} <= REQUIRED_SERVE_FIELDS


def test_serve_record_schema_pins_dedup_columns():
    """ISSUE 19 satellite: the dedup plane's counters — result-cache
    traffic and coalesced fan-outs — are part of the pinned serve
    record, and the --hot-mix record pins the full acceptance surface
    (the baseline-vs-hot QPS multiplier, the hot-phase hit rate, and
    the staleness audit)."""
    from cylon_tpu.serve.bench import (REQUIRED_HOTMIX_FIELDS,
                                       REQUIRED_SERVE_FIELDS)

    dedup = {"result_cache_hits", "result_cache_misses",
             "result_cache_invalidations", "coalesced"}
    assert dedup <= REQUIRED_SERVE_FIELDS
    assert dedup | {"baseline_qps", "hot_qps", "qps_multiplier",
                    "cache_hit_rate", "stale_results",
                    "shed"} <= REQUIRED_HOTMIX_FIELDS


def _result_cache_call_sites(path: pathlib.Path) -> list:
    """Every ``<cache>.lookup(...)`` / ``<cache>.store(...)`` call in
    ``path`` whose receiver is a result cache (a name containing
    ``result_cache``, or the bare name ``cache``), as
    ``(lineno, method, positional_argc)`` triples."""
    tree = ast.parse(path.read_text(), filename=str(path))
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute) \
                or f.attr not in ("lookup", "store"):
            continue
        recv = f.value
        rname = (recv.attr if isinstance(recv, ast.Attribute)
                 else getattr(recv, "id", ""))
        if "result_cache" not in str(rname) and str(rname) != "cache":
            continue
        out.append((node.lineno, f.attr, len(node.args)))
    return out


def test_result_cache_calls_always_pass_version_vector():
    """ISSUE 19 satellite: NO result-cache call site may key on the
    query fingerprint alone — the table-version vector is the half of
    the key that makes serving pre-append bytes after an append
    unrepresentable. Both halves are required POSITIONAL arguments of
    ``ResultCache.lookup``/``store``, so the lint walks every call in
    the tree and asserts the vector is actually passed (lookup needs
    >= 2 positional args, store >= 3: fingerprint, versions, value)."""
    found = 0
    for path in sorted((REPO / "cylon_tpu").rglob("*.py")):
        for lineno, meth, argc in _result_cache_call_sites(path):
            found += 1
            need = 2 if meth == "lookup" else 3
            assert argc >= need, (
                f"{path.relative_to(REPO)}:{lineno} calls result-cache "
                f".{meth}() with {argc} positional arg(s) — the "
                "version vector must ride the key (fingerprint-only "
                "keying would serve stale bytes across appends)")
    # the engine admission path and the fleet router both hit the
    # cache — if the lint finds neither, it is walking nothing
    assert found >= 3, f"expected >=3 result-cache call sites, {found}"


# ----------------------------------------- checkpoint/journal guards
def test_every_ooc_entrypoint_accepts_resume_dir():
    """ISSUE 8 satellite: every public out-of-core entrypoint must
    accept ``resume_dir`` — a new OOC pass shipped without the
    checkpoint hook would silently re-create the non-resumable class
    of multi-hour run this PR exists to kill."""
    path = REPO / "cylon_tpu" / "outofcore.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    ops = [n for n in ast.iter_child_nodes(tree)
           if isinstance(n, _FN) and n.name.startswith("ooc_")]
    assert len(ops) >= 3, "OOC entrypoint surface unexpectedly small"
    bare = []
    for fn in ops:
        names = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                 + fn.args.kwonlyargs)}
        if "resume_dir" not in names:
            bare.append(fn.name)
    assert not bare, (
        f"OOC entrypoints without resume_dir: {bare} — thread them "
        "through resilience.CheckpointedRun like the others")


def _serve_engine_methods():
    path = REPO / "cylon_tpu" / "serve" / "service.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    cls = next(n for n in ast.iter_child_nodes(tree)
               if isinstance(n, ast.ClassDef)
               and n.name == "ServeEngine")
    return [n for n in ast.iter_child_nodes(cls) if isinstance(n, _FN)]


def _method_calls(fn: "ast.FunctionDef", attr: str) -> list:
    """Line numbers of every ``<x>.<attr>(...)`` call inside ``fn``."""
    out = []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == attr):
            out.append(node.lineno)
    return out


def test_write_ahead_invariant_journal_before_dispatch():
    """ISSUE 8 satellite, enforced statically: the ONLY place ops
    enter the scheduler's execution set is ``_dispatch``, and every
    submission path that reaches ``_dispatch`` must write the
    write-ahead journal (``_journal_admit``) FIRST — a future
    submission path that skips the journal would make its requests
    unrecoverable, invisibly."""
    methods = _serve_engine_methods()
    dispatchers = [m.name for m in methods
                   if _method_calls(m, "add_op")]
    assert dispatchers == ["_dispatch"], (
        f"ops enter the scheduler outside _dispatch: {dispatchers}")
    submitters = [m for m in methods if _method_calls(m, "_dispatch")]
    assert submitters, "no submission path reaches _dispatch"
    for m in submitters:
        journal_lines = _method_calls(m, "_journal_admit")
        assert journal_lines, (
            f"ServeEngine.{m.name} dispatches without journaling — "
            "the write-ahead invariant is broken")
        assert min(journal_lines) < min(_method_calls(m, "_dispatch")), (
            f"ServeEngine.{m.name} journals AFTER dispatch — a kill "
            "in between loses an already-running request")


def test_durable_mutations_maintain_catalog_snapshot():
    """register_table/append_table/drop_table on a durable engine must
    keep the snapshot in sync (the tables — and, since ISSUE 18, the
    generation stamps — recover() restores)."""
    methods = {m.name: m for m in _serve_engine_methods()}
    assert _method_calls(methods["register_table"], "save")
    assert _method_calls(methods["append_table"], "save")
    assert _method_calls(methods["drop_table"], "drop")


# ------------------------------------------------- ops-plane guards
#: mutating surfaces an introspection handler must never reach — the
#: endpoint is read-only by contract (ISSUE 9), and this lint makes
#: that contract survive future handlers
_INTROSPECT_FORBIDDEN = frozenset({
    "submit", "submit_named", "register_table", "register_query",
    "drop_table", "drop", "remove_table", "put_table", "pin", "unpin",
    "clear", "reset", "close", "recover", "session", "read_csv",
    "join_tables", "sort_table", "unique_table",
    # views subsystem mutators (ISSUE 18): /views reads stats only
    # (catalog.append itself can't be named here — the attr lint
    # would trip on every list.append)
    "append_table", "register_view", "refresh_view", "drop_view",
})


def test_introspect_handlers_are_read_only():
    """ISSUE 9 satellite: every HTTP handler in serve/introspect.py is
    statically read-only — no call to any submission/registration/
    drop/close surface. A future endpoint that mutated engine state
    would turn an unauthenticated diagnostic port into a control
    plane."""
    path = REPO / "cylon_tpu" / "serve" / "introspect.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute):
            if node.func.attr in _INTROSPECT_FORBIDDEN:
                bad.append((node.lineno, node.func.attr))
    assert not bad, (
        f"introspect.py reaches mutating surfaces {bad} — the ops "
        "endpoint must stay read-only")
    # and the only HTTP verb implemented is GET
    verbs = {n.name for n in ast.walk(tree)
             if isinstance(n, _FN) and n.name.startswith("do_")}
    assert verbs == {"do_GET"}, f"non-GET handlers defined: {verbs}"


def test_query_profile_schema_pinned():
    """ISSUE 9 satellite: a real request's ``QueryTicket.profile()``
    carries every REQUIRED_PROFILE_FIELDS key and survives a strict
    JSON round trip."""
    import json

    from cylon_tpu.serve import ServeEngine, ServePolicy
    from cylon_tpu.telemetry.profile import REQUIRED_PROFILE_FIELDS

    eng = ServeEngine(policy=ServePolicy(max_queue=2))
    tk = eng.submit(lambda: 1, tenant="schema")
    assert tk.result(30) == 1
    prof = tk.profile()
    eng.close()
    assert prof is not None
    missing = [k for k in REQUIRED_PROFILE_FIELDS if k not in prof]
    assert not missing, f"profile dropped pinned fields {missing}"
    json.loads(json.dumps(prof, allow_nan=False))


def test_serve_record_schema_pins_attribution_columns():
    """ISSUE 9 satellite: the serve bench record must keep the slowest
    request's profile block and the run's HBM peak watermark."""
    from cylon_tpu.serve.bench import REQUIRED_SERVE_FIELDS

    assert {"slowest_profile",
            "peak_live_bytes"} <= REQUIRED_SERVE_FIELDS


def test_trace_record_schema_pins_dropped_count():
    """ISSUE 9 satellite: silent trace loss is surfaced — the --trace
    record must carry trace_dropped so a windowed (lossy) artifact is
    distinguishable from a complete one."""
    import bench

    assert "trace_dropped" in bench.REQUIRED_TRACE_FIELDS


# ------------------------------------------- spill-fallback guards
def test_fallback_manifest_covers_every_query():
    """ISSUE 10 satellite, tightened by ISSUE 16: every TPC-H query has
    a FALLBACK entry with a NON-None plan (the ``why`` escape hatch is
    retired — no query is allowed to be non-decomposable), whose
    partition plan is consistent with the projection manifest — the
    partitioned tables are tables the query reads, and every partition
    key survives the manifest-pruned ingest (a dropped key would make
    the spill path KeyError at scale, invisibly at test SF)."""
    from cylon_tpu.tpch.manifest import FALLBACK, MANIFEST
    from cylon_tpu.tpch.twophase import PLANS

    assert set(FALLBACK) == set(MANIFEST), (
        "FALLBACK and MANIFEST must cover the same 22 queries")
    kinds = {"concat", "groupby", "sum", "twophase"}
    for q, spec in FALLBACK.items():
        assert spec.get("merge") in kinds, (
            f"{q}: merge {spec.get('merge')!r} — every query must "
            "carry a real plan (None retired by ISSUE 16)")
        assert "why" not in spec, (
            f"{q}: the 'why' non-decomposable escape hatch is retired")
        assert spec.get("partition"), f"{q}: no partition plan"
        for table, key in spec["partition"].items():
            assert table in MANIFEST[q], (
                f"{q}: partitions {table}, which it never reads")
            if key is not None:
                assert key in MANIFEST[q][table], (
                    f"{q}: partition key {key} not in the projection "
                    f"manifest for {table} — pruned ingest would drop "
                    "it")
        if spec["merge"] == "groupby":
            assert spec.get("by") and spec.get("aggs"), q
            for col, how in spec["aggs"].items():
                if isinstance(how, tuple):
                    kind, weight = how
                    assert kind == "wmean" and weight in spec["aggs"]
                else:
                    assert how in ("sum", "min", "max"), (q, col, how)
        if spec["merge"] == "twophase":
            assert q in PLANS, (
                f"{q}: merge='twophase' but tpch.twophase.PLANS has "
                "no entry — tpch_fallback would die at run time")
        if spec.get("sort"):
            asc = spec.get("ascending")
            assert asc is None or len(asc) == len(spec["sort"]), q
    # and the executor agrees: all 22 are supported end to end
    from cylon_tpu.fallback import supports

    assert all(supports(q) for q in FALLBACK)


def test_serve_replay_queries_have_usable_fallback():
    """ISSUE 10 satellite (CI lint): every query the serve bench
    replays must have a USABLE spill plan — a served query without one
    could only fail under memory pressure, never degrade."""
    from cylon_tpu.fallback import supports
    from cylon_tpu.serve.bench import DEFAULT_MIX

    bare = [q for q in DEFAULT_MIX if not supports(q)]
    assert not bare, (
        f"serve-replay queries without a fallback plan: {bare} — add "
        "a tpch.manifest.FALLBACK entry with a non-None merge")


def test_required_bench_keys_pin_fallback_counter():
    """ISSUE 10 satellite: ooc.fallbacks rides every bench record's
    metrics block, so the trajectory shows WHICH runs degraded.
    ISSUE 16 adds the two-phase accounting: merge phases run and
    checkpoint units resumed (the ``op=fallback_merge`` label rides
    the summed counter) are pinned alongside."""
    from cylon_tpu.telemetry import REQUIRED_BENCH_KEYS

    assert {"ooc.fallbacks", "ooc.merge_phases",
            "ooc.units_resumed"} <= set(REQUIRED_BENCH_KEYS)


def test_scale_race_legs_pinned():
    """ISSUE 16 satellite: the three at-scale race configs the paper's
    claim is about (SF10 full suite, the 1B-row join, SF100 Q3/Q5) are
    named bench_suite legs, each pinning the single-chip HBM ceiling
    so in_core-vs-ooc_fallback routing matches the real chip."""
    import bench_suite

    legs = dict(bench_suite.SCALE_LEGS)
    assert set(legs) == {"tpch_sf10_full", "join_1b",
                         "tpch_sf100_q3q5"}
    assert legs["tpch_sf10_full"]["CYLON_BENCH_TPCH_SF"] == "10"
    assert legs["join_1b"]["CYLON_BENCH_ROWS"] == "1000000000"
    assert legs["tpch_sf100_q3q5"]["CYLON_BENCH_TPCH_QUERIES"] == "q3,q5"
    for name, env in legs.items():
        assert int(env["CYLON_TPU_HBM_BUDGET_BYTES"]) == 16 * 2**30, (
            f"{name}: the race must pin the v5e 16 GiB ceiling")


def test_profile_schema_pins_degradation_columns():
    """A degraded request must be self-explaining: the profile schema
    pins degraded + the fallback attribution block."""
    from cylon_tpu.telemetry.profile import REQUIRED_PROFILE_FIELDS

    assert {"degraded", "fallback"} <= set(REQUIRED_PROFILE_FIELDS)


def test_serve_record_schema_pins_degraded_column():
    from cylon_tpu.serve.bench import REQUIRED_SERVE_FIELDS

    assert "degraded" in REQUIRED_SERVE_FIELDS


# ------------------------------------------- windowed-plane guards
def _emit_call_kinds() -> list:
    """Every literal event kind passed to an ``events.emit("<kind>")``
    / ``_events.emit("<kind>")`` call anywhere under cylon_tpu/ —
    (path, lineno, kind) triples."""
    out = []
    for path in sorted((REPO / "cylon_tpu").rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit"):
                continue
            base = node.func.value
            name = (base.attr if isinstance(base, ast.Attribute)
                    else getattr(base, "id", None))
            if name not in ("events", "_events"):
                continue
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                out.append((str(path.relative_to(REPO)),
                            node.lineno, node.args[0].value))
    return out


def test_every_emitted_event_kind_is_registered():
    """ISSUE 14 satellite (CI lint): every literal event kind emitted
    anywhere in the tree is registered in the events schema — an
    unregistered kind would raise at RUNTIME only on the armed path,
    i.e. exactly when someone is debugging an incident."""
    from cylon_tpu.telemetry.events import EVENT_KINDS

    sites = _emit_call_kinds()
    assert len(sites) >= 10, (
        f"event emit surface unexpectedly small: {sites}")
    bad = [(p, ln, k) for p, ln, k in sites if k not in EVENT_KINDS]
    assert not bad, (
        f"emit() calls with unregistered event kinds: {bad} — add "
        "them to telemetry.events.EVENT_KINDS")
    # and the core serve-storm vocabulary is actually wired somewhere
    emitted = {k for _, _, k in sites}
    assert {"admit", "retire", "shed", "degraded", "oom",
            "breaker_open", "breaker_close", "checkpoint_resume",
            "fallback", "watchdog_expired"} <= emitted, emitted


def test_introspect_surface_covers_windowed_endpoints():
    """ISSUE 14 satellite: the read-only AST lint above walks ALL of
    introspect.py, so it is enough that /health, /events and
    /metrics/window are routed THERE (and advertised) — this pins
    exactly that, so the handlers can never move out from under the
    lint."""
    from cylon_tpu.serve import introspect

    assert {"/health", "/events", "/metrics/window",
            "/healthz"} <= set(introspect.ENDPOINTS)
    path = REPO / "cylon_tpu" / "serve" / "introspect.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    # anchor on the DISPATCH, not the ENDPOINTS advertisement: the
    # string constants inside the _route handler itself — so moving a
    # handler out of the linted file (while still advertising it)
    # fails here
    route_fn = next(n for n in ast.walk(tree)
                    if isinstance(n, _FN) and n.name == "_route")
    routed = {n.value for n in ast.walk(route_fn)
              if isinstance(n, ast.Constant)
              and isinstance(n.value, str) and n.value.startswith("/")}
    for ep in ("/health", "/events", "/metrics/window", "/healthz"):
        assert ep in routed, f"{ep} not dispatched inside _route"


def test_serve_record_schema_pins_windowed_columns():
    """ISSUE 14 satellite: the serve record keeps the windowed p99 and
    SLO burn columns (main() asserts the set before emitting)."""
    from cylon_tpu.serve.bench import REQUIRED_SERVE_FIELDS

    assert {"windowed_p99_s", "slo_burn"} <= REQUIRED_SERVE_FIELDS


# ------------------------------------------------- fleet guards
def test_fleet_record_schema_pinned():
    """ISSUE 15 satellite: the --fleet record must keep the engine
    count, failover/replay counters, the lost-ack and double-execution
    audits and the p99 before/during/after the kill (main() asserts
    the set before emitting, so the pin is enforced at bench runtime
    too)."""
    from cylon_tpu.serve.bench import REQUIRED_FLEET_FIELDS

    assert {"engines", "failovers", "lost_acks", "replayed",
            "double_executions", "p99_before_s", "p99_during_s",
            "p99_after_s"} <= REQUIRED_FLEET_FIELDS
    src = (REPO / "cylon_tpu" / "serve" / "bench.py").read_text()
    assert "REQUIRED_FLEET_FIELDS - record.keys()" in src


#: ServeEngine/scheduler internals the fleet router must NEVER touch —
#: the router has to work CROSS-PROCESS, so anything it needs must be
#: reachable through the public HTTP/engine API; a private-attr
#: shortcut here would only work in-process and rot silently
_FLEET_FORBIDDEN = frozenset({
    "_dispatch", "_exec", "_loop", "_retire", "_admission",
    "_journal", "_snapshot", "_idem", "_queries", "_recent",
    "_slo", "_last_sweep", "_profiler", "_undo_admission",
    "_journal_admit", "_evict_idem_locked", "_cond", "_closed",
    "_closing",
})


def test_fleet_router_talks_only_public_engine_api():
    """ISSUE 15 satellite (CI lint): serve/fleet.py reaches engines
    only through their public surface (submit_named/ticket/health/
    closing/close/... or HTTP) — no attribute access to scheduler or
    journal internals anywhere in the module."""
    path = REPO / "cylon_tpu" / "serve" / "fleet.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    bad = [(n.lineno, n.attr) for n in ast.walk(tree)
           if isinstance(n, ast.Attribute)
           and n.attr in _FLEET_FORBIDDEN]
    assert not bad, (
        f"fleet.py reaches engine internals {bad} — the router must "
        "work cross-process through the public HTTP/engine API only")


def test_fleet_poll_runs_under_registered_router_poll_section():
    """The router's poll loop runs under the NAMED router_poll
    watchdog section, registered (retryable) in both registries."""
    from cylon_tpu import watchdog
    from cylon_tpu.config import DEADLINE_SECTIONS

    secs = _watchdog_section_constants(
        REPO / "cylon_tpu" / "serve" / "fleet.py")
    assert "router_poll" in secs, (
        "fleet.py no longer polls under the router_poll section")
    assert watchdog.SECTIONS.get("router_poll") is True
    assert "router_poll" in DEADLINE_SECTIONS


def test_checker_accepts_closures_and_comprehensions(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text(
        "import os\n"
        "X = 1\n"
        "def outer(a):\n"
        "    acc = []\n"
        "    def inner(b):\n"
        "        acc.append(a + b + X)\n"
        "    vals = [y * 2 for y in range(a)]\n"
        "    f = lambda z: z + a\n"
        "    with open(os.devnull) as fh:\n"
        "        pass\n"
        "    return inner, vals, f, fh\n"
    )
    assert undefined_names(p) == []


# ------------------------------------------- ooc-overlap guards
def test_ooc_overlap_record_schema_pinned():
    """ISSUE 13 satellite: the overlap A/B verdict is only auditable
    if every --ooc-overlap record pins the op, source model, BOTH
    walls, the prefetch counters, the hidden-IO seconds, the
    per-stage idle fractions and the trace artifact path — and the
    harness asserts the schema before emitting."""
    import bench

    assert {"op", "rows", "source", "sequential_wall", "overlap_wall",
            "overlap_speedup", "prefetch_hits", "prefetch_misses",
            "overlap_seconds", "prefetch_compute_overlap_s",
            "idle_fractions_sequential", "idle_fractions_overlap",
            "platform", "trace_path"} <= bench.REQUIRED_OOC_OVERLAP_FIELDS
    src = (REPO / "bench.py").read_text()
    assert "REQUIRED_OOC_OVERLAP_FIELDS - record.keys()" in src


def _fn_references(fn: "ast.FunctionDef") -> set:
    """Every Name load + Attribute attr referenced inside ``fn``."""
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, ast.Attribute):
            out.add(node.attr)
    return out


def test_every_ooc_entrypoint_routes_ingest_through_prefetcher():
    """ISSUE 13 satellite (CI lint): chunk ingest has ONE funnel —
    ``_resolve_source`` → ``pipeline.prefetched`` — and every public
    ``ooc_*`` entrypoint must route through it; the per-unit device
    ingest loops of ooc_join/ooc_sort (and fallback's partition loop)
    must ride ``pipeline.prefetch_map``, and every pass's durable
    commits must ride ``pipeline.committer``. A later PR adding a
    sequential side-door (a pass that iterates its source directly)
    would silently regress the overlap this PR measured."""
    path = REPO / "cylon_tpu" / "outofcore.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    fns = {n.name: n for n in ast.iter_child_nodes(tree)
           if isinstance(n, _FN)}
    ops = [n for n in fns.values() if n.name.startswith("ooc_")]
    assert len(ops) >= 3, "OOC entrypoint surface unexpectedly small"
    # the shared funnel itself prefetches
    assert "prefetched" in _fn_references(fns["_resolve_source"]), (
        "_resolve_source no longer routes chunk ingest through "
        "pipeline.prefetched — the shared-prefetcher funnel is gone")
    for fn in ops:
        refs = _fn_references(fn)
        assert "_resolve_source" in refs, (
            f"{fn.name} ingests chunks outside _resolve_source — a "
            "sequential side-door around the shared prefetcher")
        assert "committer" in refs, (
            f"{fn.name} commits units outside pipeline.committer — "
            "its spill writes no longer overlap compute")
    for name in ("ooc_join", "ooc_sort"):
        assert "prefetch_map" in _fn_references(fns[name]), (
            f"{name}'s per-unit device ingest no longer rides "
            "pipeline.prefetch_map")
    # the fallback executor's partition loop too
    fpath = REPO / "cylon_tpu" / "fallback.py"
    ftree = ast.parse(fpath.read_text(), filename=str(fpath))
    ffns = {n.name: n for n in ast.iter_child_nodes(ftree)
            if isinstance(n, _FN)}
    frefs = _fn_references(ffns["tpch_fallback"])
    assert {"prefetch_map", "committer"} <= frefs, (
        "tpch_fallback's partition loop left the pipelined executor")


# ------------------------------------------- hash-join A/B guards
def test_join_ab_record_schema_pinned():
    """ISSUE 12 satellite: the A/B verdict is only reproducible if
    every --join-ab record pins the config, both walls, the winner and
    the overflow-fallback count."""
    import bench

    assert bench.REQUIRED_JOIN_AB_FIELDS == frozenset({
        "rows", "distribution", "sort_wall", "hash_wall", "winner",
        "overflow_fallbacks"})
    # and the harness asserts the schema before emitting
    src = (REPO / "bench.py").read_text()
    assert "REQUIRED_JOIN_AB_FIELDS - record.keys()" in src


def _pallas_entry_points():
    """Public functions of ops/pallas_kernels.py that (directly or via
    their one-hop private impl) invoke ``pl.pallas_call`` — the kernel
    entry points the interpret-mode test contract covers."""
    src = (REPO / "cylon_tpu/ops/pallas_kernels.py").read_text()
    tree = ast.parse(src)
    fns = {n.name: n for n in tree.body
           if isinstance(n, ast.FunctionDef)}

    def has_pallas_call(fn):
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "pallas_call":
                return True
        return False

    def calls(fn):
        out = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Name):
                out.add(node.func.id)
        return out

    entry = []
    for name, fn in fns.items():
        if name.startswith("_"):
            continue
        if has_pallas_call(fn) or any(
                c in fns and has_pallas_call(fns[c])
                for c in calls(fn)):
            entry.append(name)
    return entry


def test_every_pallas_kernel_has_an_interpret_mode_test():
    """ISSUE 12 satellite (CI lint): every Pallas kernel entry point
    must be referenced from a test file that forces interpret mode —
    otherwise the kernel code path only ever executes on real TPUs and
    a regression ships invisibly past tier-1."""
    entries = _pallas_entry_points()
    assert {"row_hash", "scan32", "pair_max_scan", "bucket_build",
            "bucket_probe"} <= set(entries), entries
    tests = {p: p.read_text() for p in (REPO / "tests").glob("test_*.py")}
    interpret_tests = {p: t for p, t in tests.items()
                       if 'setenv("CYLON_PALLAS", "1")' in t}
    assert interpret_tests, "no interpret-mode test files found"
    blob = "\n".join(interpret_tests.values())
    missing = [e for e in entries if e not in blob]
    assert not missing, (
        f"Pallas kernel entry points with no interpret-mode test "
        f"reference: {missing}")


def test_profile_schema_pins_join_routing():
    """ISSUE 12 satellite: the ANALYZE profile must keep the join
    routing block (which kernel actually ran)."""
    from cylon_tpu.telemetry.profile import (REQUIRED_PROFILE_FIELDS,
                                             _COUNTERS)

    assert "join" in REQUIRED_PROFILE_FIELDS
    assert "join.algorithm" in _COUNTERS
    assert "join.overflow_fallbacks" in _COUNTERS


# ------------------------------------------------- views guards
def test_refresh_record_schema_pinned():
    """ISSUE 18 satellite: the --refresh record must keep the
    incremental-vs-recompute walls, the speedup ratio, the generation
    lag and the oracle audit (main() asserts the set before emitting,
    so the pin is enforced at bench runtime too)."""
    from cylon_tpu.serve.bench import REQUIRED_REFRESH_FIELDS

    assert {"refresh_wall_s", "recompute_wall_s", "speedup",
            "generation_lag", "oracle_mismatches", "delta_rows_total",
            "appends", "refreshes", "views"} <= REQUIRED_REFRESH_FIELDS
    src = (REPO / "cylon_tpu" / "serve" / "bench.py").read_text()
    assert "REQUIRED_REFRESH_FIELDS - record.keys()" in src


def test_view_event_kinds_registered_and_emitted():
    """ISSUE 18 satellite: the append / view_refresh kinds are in the
    typed schema AND actually wired at their owning call sites — the
    rglob-based emit lint above covers cylon_tpu/views/ by
    construction, this pins that the sites exist at all."""
    from cylon_tpu.telemetry.events import EVENT_KINDS

    assert {"append", "view_refresh"} <= set(EVENT_KINDS)
    sites = _emit_call_kinds()
    by_kind = {}
    for p, _, k in sites:
        by_kind.setdefault(k, set()).add(p)
    assert "cylon_tpu/catalog.py" in by_kind.get("append", set())
    assert ("cylon_tpu/views/materialized.py"
            in by_kind.get("view_refresh", set()))


def test_views_endpoint_routed_through_introspect():
    """The /views payload rides the same read-only introspection
    surface the ops-plane lint walks."""
    from cylon_tpu.serve import introspect

    assert "/views" in introspect.ENDPOINTS


# ------------------------------------------- fleet-trace guards
def test_fleet_trace_record_schema_pinned():
    """ISSUE 20 satellite: the --fleet-trace record must pin the
    stitched-artifact surface — where the Chrome trace landed, the
    span and engine-track counts, the clock-handshake jitter bound and
    the replay-hop count — and main() asserts the set before
    emitting."""
    from cylon_tpu.serve.bench import REQUIRED_FLEET_TRACE_FIELDS

    assert REQUIRED_FLEET_TRACE_FIELDS == frozenset({
        "trace_path", "spans", "engines_stitched", "offset_jitter_s",
        "replay_hops"})
    src = (REPO / "cylon_tpu" / "serve" / "bench.py").read_text()
    assert "REQUIRED_FLEET_TRACE_FIELDS - record.keys()" in src


def test_trace_endpoint_routed_through_introspect():
    """ISSUE 20 satellite: /trace rides the SAME statically read-only
    introspection surface as /events — advertised in ENDPOINTS and
    dispatched inside introspect._route, so the mutating-call lint
    above covers it by construction and it can never quietly move to
    a writable port."""
    from cylon_tpu.serve import introspect

    assert "/trace" in introspect.ENDPOINTS
    path = REPO / "cylon_tpu" / "serve" / "introspect.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    route_fn = next(n for n in ast.walk(tree)
                    if isinstance(n, _FN) and n.name == "_route")
    routed = {n.value for n in ast.walk(route_fn)
              if isinstance(n, ast.Constant)
              and isinstance(n.value, str) and n.value.startswith("/")}
    assert "/trace" in routed, "/trace not dispatched inside _route"


def test_dedup_event_kinds_registered_and_emitted():
    """ISSUE 20 satellite (extends the literal-emit lint): the PR 19
    dedup-plane outcomes — cache_hit, coalesced, batch_retire — and
    the router's events_gap are in the typed schema AND wired at their
    owning call sites (service.py for the engine-side three, fleet.py
    for the gap counter)."""
    from cylon_tpu.telemetry.events import EVENT_KINDS

    assert {"cache_hit", "coalesced", "batch_retire",
            "events_gap"} <= set(EVENT_KINDS)
    by_kind: dict = {}
    for p, _, k in _emit_call_kinds():
        by_kind.setdefault(k, set()).add(p)
    for kind in ("cache_hit", "coalesced", "batch_retire"):
        assert "cylon_tpu/serve/service.py" in by_kind.get(kind, set()), (
            f"{kind} is registered but never emitted from the serve "
            "engine")
    assert "cylon_tpu/serve/fleet.py" in by_kind.get("events_gap",
                                                     set())


def _class_method(tree: ast.Module, cls: str, meth: str):
    cnode = next(n for n in ast.walk(tree)
                 if isinstance(n, ast.ClassDef) and n.name == cls)
    return next(n for n in ast.iter_child_nodes(cnode)
                if isinstance(n, _FN) and n.name == meth)


def _string_constants(fn) -> set:
    return {n.value for n in ast.walk(fn)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def test_every_fleet_submit_path_stamps_trace_context():
    """ISSUE 20 satellite (CI lint): each hop of a fleet request's
    admission chain must carry the trace context — the gateway's POST
    handler reads the X-Cylon-Trace-Id header into submit_named's
    control kwargs, the router's submit mints the id and opens the
    fleet.submit span, and the failover replay re-enters the ORIGINAL
    id with a fleet.replay_hop marker. A future submit path added
    without these would produce requests that silently vanish from
    stitched timelines."""
    path = REPO / "cylon_tpu" / "serve" / "fleet.py"
    tree = ast.parse(path.read_text(), filename=str(path))

    post = _class_method(tree, "EngineGateway", "_post")
    assert "X-Cylon-Trace-Id" in _string_constants(post), (
        "EngineGateway._post no longer reads the trace header")
    assert "_trace_id" in _string_constants(post) \
        or "_trace_id" in {kw.arg for n in ast.walk(post)
                           if isinstance(n, ast.Call)
                           for kw in n.keywords}, (
        "EngineGateway._post no longer forwards _trace_id to "
        "submit_named")

    submit = _class_method(tree, "FleetRouter", "submit")
    refs = _fn_references(submit)
    assert {"new_trace_id", "trace_context"} <= refs, (
        "FleetRouter.submit no longer mints/enters the trace context")
    assert "fleet.submit" in _string_constants(submit), (
        "FleetRouter.submit no longer opens the fleet.submit span")

    replay = _class_method(tree, "FleetRouter", "_replay_journal")
    assert "trace_context" in _fn_references(replay), (
        "_replay_journal no longer re-enters the original trace id")
    assert "fleet.replay_hop" in _string_constants(replay), (
        "_replay_journal no longer marks the replay hop")

    # and the engine side accepts the propagated context as control
    # kwargs (stripped before fingerprinting)
    from cylon_tpu.serve.service import ServeEngine

    assert {"_trace_id",
            "_parent_span"} <= set(ServeEngine._CONTROL_KW)
