"""Incremental materialized views (ISSUE 18): delta-merge algebra
proofs, appendable version-digested catalog tables, view refresh
semantics, serve integration (append_table / recover() generation
restore), and kill-mid-refresh resume.

The algebra proofs pin the subsystem's core claim per merge kind:

    merge(view(base), view(delta)) == view(base ++ delta)

including the empty-delta and all-duplicate-key edges. Float sums
re-associate across the merge, so float columns compare at the
repo-standard ``rtol=1e-9``; keys, counts and row sets compare
exactly.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import cylon_tpu  # noqa: F401  (x64 init)
from cylon_tpu import catalog, telemetry, views
from cylon_tpu.errors import InvalidArgument, KeyError_
from cylon_tpu.resilience import KILL_EXIT_CODE
from cylon_tpu.table import Table
from cylon_tpu.testing import assert_results_match
from cylon_tpu.views import (combine_partials, finalize_twophase,
                             merge_delta, present)

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean():
    catalog.clear()
    views.clear()
    yield
    catalog.clear()
    views.clear()


def _frames_equal(got, want, float_cols=()):
    """Exact on keys/counts, rtol=1e-9 on re-associated float sums."""
    assert_results_match(got.reset_index(drop=True)[list(want.columns)],
                         want, float_cols=float_cols)


# ====================================================== merge algebra
GB_SPEC = {"merge": "groupby", "by": ["k"],
           "aggs": {"s": "sum", "mx": "max",
                    "avg": ("wmean", "n"), "n": "sum"},
           "sort": ["k"]}


def _gb_view(df):
    """A q1-shaped partial: sums, a max, a mean with its count
    weight."""
    if not len(df):
        return df.head(0).assign(s=0.0, mx=0.0, avg=0.0, n=0.0)[
            ["k", "s", "mx", "avg", "n"]]
    g = df.groupby("k", as_index=False, sort=False)
    out = g.agg(s=("v", "sum"), mx=("v", "max"), avg=("v", "mean"),
                n=("v", "size"))
    out["n"] = out["n"].astype(np.float64)
    return out


def _rand(rng, n, keys):
    return pd.DataFrame({"k": rng.choice(keys, size=n),
                         "v": rng.normal(size=n)})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_groupby_merge_equals_view_of_concat(seed):
    rng = np.random.default_rng(seed)
    base = _rand(rng, 200, np.arange(6))
    delta = _rand(rng, 57, np.arange(3, 9))  # overlap + new groups
    got = present(merge_delta(_gb_view(base), _gb_view(delta),
                              GB_SPEC), GB_SPEC)
    want = present(_gb_view(pd.concat([base, delta],
                                      ignore_index=True)), GB_SPEC)
    _frames_equal(got, want, float_cols=("s", "mx", "avg"))
    assert list(got["n"]) == list(want["n"])


def test_groupby_merge_empty_delta_and_all_duplicate_keys():
    rng = np.random.default_rng(3)
    base = _rand(rng, 120, np.arange(4))
    # empty delta: the state passes through unchanged (up to sort)
    got = present(merge_delta(_gb_view(base), _gb_view(base.head(0)),
                              GB_SPEC), GB_SPEC)
    _frames_equal(got, present(_gb_view(base), GB_SPEC),
                  float_cols=("s", "mx", "avg"))
    # every delta key already present: pure re-aggregation, no new rows
    delta = _rand(rng, 50, np.arange(4))
    got = present(merge_delta(_gb_view(base), _gb_view(delta),
                              GB_SPEC), GB_SPEC)
    want = present(_gb_view(pd.concat([base, delta],
                                      ignore_index=True)), GB_SPEC)
    assert len(got) == base["k"].nunique()
    _frames_equal(got, want, float_cols=("s", "mx", "avg"))


C_SPEC = {"merge": "concat", "sort": ["rev", "k"],
          "ascending": [False, True], "partition": {"t": "k"}}


def _c_view(df):
    """A q3-shaped partial: one output row per partition-closed key."""
    if not len(df):
        return pd.DataFrame({"k": np.empty(0, np.int64),
                             "rev": np.empty(0, np.float64)})
    return df.groupby("k", as_index=False, sort=False).agg(
        rev=("v", "sum"))


def test_concat_merge_topk_exact_across_sides():
    """Untruncated state + limit at present(): the top-k is exact even
    when the true top rows split across base and delta."""
    rng = np.random.default_rng(4)
    base = _rand(rng, 150, np.arange(0, 10))
    delta = _rand(rng, 80, np.arange(10, 18))  # partition-closed
    state = merge_delta(_c_view(base), _c_view(delta), C_SPEC)
    got = present(state, C_SPEC, limit=5)
    want = present(_c_view(pd.concat([base, delta],
                                     ignore_index=True)),
                   C_SPEC, limit=5)
    assert len(got) == 5
    _frames_equal(got, want, float_cols=("rev",))
    # the state itself stays untruncated
    assert len(state) == 18


def test_sum_merge_is_addition_and_none_is_zero():
    assert merge_delta(2.5, 1.25, {"merge": "sum"}) == 3.75
    assert merge_delta(None, 3.0, {"merge": "sum"}) == 3.0
    assert merge_delta(3.0, None, {"merge": "sum"}) == 3.0
    assert present(3.75, {"merge": "sum"}) == 3.75


# -------------------------------------------- two-phase scalar merge
@pytest.fixture(scope="module")
def tiny_tpch():
    from cylon_tpu.tpch import dbgen

    return dbgen.generate(sf=0.002, seed=0)


def _split_rows(t, alias, mask):
    lo = {k: t[k] for k in t}
    hi = {k: t[k] for k in t}
    lo[alias] = {c: np.asarray(a)[mask] for c, a in t[alias].items()}
    hi[alias] = {c: np.asarray(a)[~mask] for c, a in t[alias].items()}
    return lo, hi


def _assert_twophase_equal(got, want):
    if isinstance(got, float):
        np.testing.assert_allclose(got, want, rtol=1e-9)
    else:
        _frames_equal(got, want,
                      float_cols=[c for c in want.columns
                                  if want[c].dtype.kind == "f"])


@pytest.mark.parametrize("query,part_alias", [
    ("q14", "lineitem"),
    ("q8", "lineitem"),
])
def test_twophase_combine_matches_full_phase1(tiny_tpch, query,
                                              part_alias):
    """combine(phase1(base), phase1(delta)) finalizes to the same
    scalar/frame as phase1 over all rows — the partial IS the
    maintainable view state. q14/q8 partials are row-associative, so
    ANY row split of the partitioned table is partition-closed."""
    from cylon_tpu.tpch.twophase import PLANS

    plan = PLANS[query]
    rows = len(np.asarray(
        next(iter(tiny_tpch[part_alias].values()))))
    mask = np.arange(rows) < rows // 2
    lo, hi = _split_rows(tiny_tpch, part_alias, mask)
    state = combine_partials(query, [plan.phase1(lo), plan.phase1(hi)])
    got = finalize_twophase(query, state)
    want = finalize_twophase(query, plan.phase1(dict(tiny_tpch)))
    _assert_twophase_equal(got, want)


def test_twophase_q16_combine_needs_supplier_closed_split(tiny_tpch):
    """q16's COUNT(DISTINCT supplier) dedups inside one partial — the
    combine is exact when the split is supplier-closed (each suppkey
    wholly on one side), which is the documented exactness contract."""
    from cylon_tpu.tpch.twophase import PLANS

    plan = PLANS["q16"]
    sk = np.asarray(tiny_tpch["partsupp"]["ps_suppkey"])
    lo, hi = _split_rows(tiny_tpch, "partsupp", sk % 2 == 0)
    state = combine_partials("q16",
                             [plan.phase1(lo), plan.phase1(hi)])
    got = finalize_twophase("q16", state)
    want = finalize_twophase("q16", plan.phase1(dict(tiny_tpch)))
    _assert_twophase_equal(got, want)


def test_twophase_combine_empty_and_refusals(tiny_tpch):
    from cylon_tpu.tpch.twophase import PLANS

    p = PLANS["q14"].phase1(tiny_tpch)
    # empty side contributes nothing
    state = combine_partials("q14", [None, p])
    np.testing.assert_allclose(finalize_twophase("q14", state),
                               finalize_twophase("q14", p), rtol=1e-9)
    # plans with a phase-2 apply pass are NOT maintainable
    for q in ("q11", "q15", "q22"):
        with pytest.raises(InvalidArgument,
                           match="not view-maintainable"):
            combine_partials(q, [p])
        with pytest.raises(InvalidArgument, match="phase-2"):
            finalize_twophase(q, p)


# ==================================================== catalog appends
def _t(n=8, k0=0):
    return Table.from_pydict(
        {"k": np.arange(k0, k0 + n, dtype=np.int64),
         "v": np.arange(n, dtype=np.float64)})


def _d(n=3, k0=100):
    return pd.DataFrame({"k": np.arange(k0, k0 + n, dtype=np.int64),
                         "v": np.full(n, 0.5)})


def test_append_bumps_generation_and_digest():
    catalog.put_table("t", _t())
    v1 = catalog.table_version("t")
    assert v1["generation"] == 1 and v1["digest"]
    res = catalog.append("t", _d(3))
    assert res == {"generation": 2, "delta_rows": 3, "rows": 11}
    v2 = catalog.table_version("t")
    assert v2["generation"] == 2 and v2["digest"] != v1["digest"]
    assert catalog.generation("t") == 2
    # stats carries the version column (the /tables payload)
    st = catalog.stats()["t"]
    assert st["version"]["generation"] == 2
    assert st["rows"] == 11


def test_append_accepts_mappings_and_rejects_schema_drift():
    catalog.put_table("t", _t())
    catalog.append("t", {"k": np.array([9]), "v": np.array([1.0])})
    assert catalog.stats()["t"]["rows"] == 9
    with pytest.raises(InvalidArgument, match="resident schema"):
        catalog.append("t", pd.DataFrame({"k": [1], "wrong": [2.0]}))
    with pytest.raises(KeyError_):
        catalog.append("missing", _d())


def test_append_legal_while_pinned():
    """put_table on a pinned id is refused; append is NOT — the
    in-flight reader keeps its immutable pre-append Table."""
    catalog.put_table("t", _t())
    old = catalog.get_table("t", pin_for="reader-1")
    catalog.append("t", _d(2))
    assert catalog.get_table("t").num_rows == 10
    assert old.num_rows == 8  # the pinned generation is untouched
    catalog.unpin("t", holder="reader-1")


def test_deltas_since_covers_exact_span_or_says_none(monkeypatch):
    catalog.put_table("t", _t())
    assert catalog.deltas_since("t", 1) == []
    catalog.append("t", _d(2, k0=50))
    catalog.append("t", _d(3, k0=60))
    got = catalog.deltas_since("t", 1)
    assert [len(f) for f in got] == [2, 3]  # oldest first
    assert list(got[0]["k"]) == [50, 51]
    assert [len(f) for f in catalog.deltas_since("t", 2)] == [3]
    # a full overwrite breaks the delta chain: recompute, don't blend
    catalog.put_table("t2", _t())
    catalog.append("t2", _d())
    catalog.put_table("t2", _t(4))
    assert catalog.deltas_since("t2", 1) is None
    # retention window 0 retains nothing -> any stale watermark is None
    monkeypatch.setenv("CYLON_TPU_CATALOG_DELTA_KEEP", "0")
    catalog.put_table("t3", _t())
    catalog.append("t3", _d())
    assert catalog.deltas_since("t3", 1) is None


def test_restore_version_and_on_append_listener():
    catalog.put_table("t", _t())
    catalog.restore_version("t", 7)
    assert catalog.generation("t") == 7
    assert catalog.table_version("t")["digest"]  # recomputed lazily
    heard = []
    catalog.on_append(lambda tid, gen: heard.append((tid, gen)))
    try:
        catalog.append("t", _d())
        assert heard == [("t", 8)]
    finally:
        catalog._append_listeners.pop()


# ================================================= materialized views
def _gb_qf(tables):
    return _gb_view(tables["t"])


def _register_gb(name="agg", **kw):
    return views.register_view(name, _gb_qf, GB_SPEC,
                               sources={"t": "t"}, **kw)


def _seed_table(rng, n=200):
    df = _rand(rng, n, np.arange(6))
    catalog.put_table("t", Table.from_pydict(
        {c: df[c].to_numpy() for c in df.columns}))
    return df


def test_incremental_refresh_matches_full_recompute():
    rng = np.random.default_rng(10)
    base = _seed_table(rng)
    _register_gb()
    delta = _rand(rng, 40, np.arange(2, 8))
    catalog.append("t", delta)
    out = views.refresh("agg")
    assert out["refreshed"] and not out["full_recompute"]
    assert out["delta_rows"] == 40
    assert out["generations"] == {"t": 2}
    got = views.read("agg")
    want = present(_gb_view(pd.concat([base, delta],
                                      ignore_index=True)), GB_SPEC)
    _frames_equal(got["result"], want, float_cols=("s", "mx", "avg"))
    assert got["lag"] == 0 and got["generations"] == {"t": 2}
    # an independently-registered view over the same data digests
    # identically only if states match bit-for-bit — so compare values
    views.drop_view("agg")
    v2 = _register_gb("agg2")
    _frames_equal(present(v2.state, GB_SPEC), want,
                  float_cols=("s", "mx", "avg"))


def test_refresh_idempotent_and_empty_delta_advances_watermark():
    rng = np.random.default_rng(11)
    _seed_table(rng)
    _register_gb()
    assert views.refresh("agg")["refreshed"] is False  # nothing to do
    d0 = views.view_version("agg")["digest"]
    catalog.append("t", _rand(rng, 0, np.arange(6)))  # 0-row delta
    out = views.refresh("agg")
    assert out["refreshed"] and out["delta_rows"] == 0
    assert out["generations"] == {"t": 2}
    assert views.view_version("agg")["digest"] == d0  # state untouched
    assert views.refresh("agg")["refreshed"] is False


def test_broken_delta_span_full_recomputes(monkeypatch):
    rng = np.random.default_rng(12)
    base = _seed_table(rng)
    _register_gb()
    monkeypatch.setenv("CYLON_TPU_CATALOG_DELTA_KEEP", "0")
    delta = _rand(rng, 25, np.arange(6))
    catalog.append("t", delta)
    out = views.refresh("agg")
    assert out["refreshed"] and out["full_recompute"]
    assert out["delta_rows"] is None
    want = present(_gb_view(pd.concat([base, delta],
                                      ignore_index=True)), GB_SPEC)
    _frames_equal(views.read("agg")["result"], want,
                  float_cols=("s", "mx", "avg"))


def test_read_lag_memo_and_invalidate_hook():
    rng = np.random.default_rng(13)
    _seed_table(rng)

    calls = []

    class QF:
        def __call__(self, tables):
            return _gb_view(tables["t"])

        def invalidate(self):
            calls.append("inv")

    views.register_view("agg", QF(), GB_SPEC, sources={"t": "t"})
    r1 = views.read("agg")
    assert r1["lag"] == 0
    assert views.read("agg")["result"] is r1["result"]  # memo hit
    catalog.append("t", _rand(rng, 5, np.arange(6)))
    assert calls == ["inv"]  # plan memos evicted through the hook
    r2 = views.read("agg")
    assert r2["lag"] == 1  # stale by exactly the unapplied append
    assert r2["generations"] == {"t": 1}  # still the consistent state
    views.refresh("agg")
    assert views.read("agg")["lag"] == 0


def test_register_validation_and_registry_ops():
    rng = np.random.default_rng(14)
    _seed_table(rng)
    with pytest.raises(InvalidArgument, match="sum/concat/groupby"):
        views.register_view("v", _gb_qf, {"merge": "nope"},
                            sources={"t": "t"})
    with pytest.raises(InvalidArgument, match="maintainable"):
        views.register_view("v", _gb_qf,
                            {"merge": "twophase", "query": "q11"},
                            sources={"t": "t"})
    with pytest.raises(InvalidArgument, match="ambiguous"):
        views.register_view("v", _gb_qf, GB_SPEC,
                            sources={"t": "t", "u": "t"})
    _register_gb()
    with pytest.raises(InvalidArgument, match="already registered"):
        _register_gb()
    with pytest.raises(KeyError_, match="no view"):
        views.read("ghost")
    assert views.list_views() == ["agg"]
    st = views.stats()["agg"]
    assert st["merge"] == "groupby" and st["refreshes"] == 0
    assert st["generations"] == {"t": 1} and st["state_rows"] >= 1
    views.drop_view("agg")
    assert views.list_views() == []
    with pytest.raises(KeyError_):
        views.drop_view("agg", if_exists=False)
    # a failing initial compute rolls the registration back
    with pytest.raises(ZeroDivisionError):
        views.register_view("boom", lambda t: 1 / 0, GB_SPEC,
                            sources={"t": "t"})
    assert views.list_views() == []


def test_copartition_prune_shrinks_dimension_to_delta_keys():
    """The semi-join pushdown: on refresh, a co-partitioned dimension
    arrives pruned to the delta's key values — O(delta), not
    O(dimension)."""
    catalog.put_table("ord", Table.from_pydict(
        {"ok": np.arange(100, dtype=np.int64),
         "w": np.ones(100)}))
    catalog.put_table("li", Table.from_pydict(
        {"lk": np.arange(100, dtype=np.int64),
         "v": np.ones(100)}))
    seen = []

    def qf(tables):
        seen.append({a: len(f) for a, f in tables.items()})
        j = tables["li"].merge(tables["ord"], left_on="lk",
                               right_on="ok")
        return float((j["v"] * j["w"]).sum())

    spec = {"merge": "sum",
            "partition": {"li": "lk", "ord": "ok"}}
    views.register_view("rev", qf, spec, sources={"li": "li",
                                                  "ord": "ord"},
                        delta_source="li")
    assert seen[-1] == {"li": 100, "ord": 100}  # full initial compute
    catalog.append("ord", pd.DataFrame({"ok": [100, 101],
                                        "w": [2.0, 2.0]}))
    catalog.append("li", pd.DataFrame({"lk": [100, 101],
                                       "v": [3.0, 4.0]}))
    out = views.refresh("rev")
    assert out["refreshed"] and not out["full_recompute"]
    # delta saw 2 lineitem rows and a 2-row pruned dimension
    assert seen[-1] == {"li": 2, "ord": 2}
    assert views.read("rev")["result"] == 100.0 + 3.0 * 2 + 4.0 * 2


def test_refresh_emits_telemetry_and_events(monkeypatch):
    from cylon_tpu.telemetry import events

    monkeypatch.setenv("CYLON_TPU_EVENTS", "1")
    events.clear()
    try:
        rng = np.random.default_rng(15)
        _seed_table(rng)
        _register_gb()
        before = telemetry.total("view.delta_rows")
        catalog.append("t", _rand(rng, 9, np.arange(6)))
        views.refresh("agg")
        assert telemetry.total("view.delta_rows") == before + 9
        assert telemetry.counter("catalog.appends",
                                 table="t").value >= 1
        kinds = [e["kind"] for e in events.events()]
        assert "append" in kinds and "view_refresh" in kinds
        vr = [e for e in events.events()
              if e["kind"] == "view_refresh"][-1]
        assert vr["view"] == "agg" and vr["delta_rows"] == 9
        assert vr["generation"] == 2 and vr["full_recompute"] is False
    finally:
        events.clear()


def test_compiled_query_invalidate_clears_plan_memos():
    from cylon_tpu import plan

    cq = plan.CompiledQuery(lambda x: x)
    cq._scale_memo["key"] = 4
    cq._compiled[("key", 4)] = object()
    cq._size_memo["key"] = 8
    cq.invalidate()
    assert not cq._scale_memo and not cq._compiled
    assert not cq._size_memo


# ==================================================== serve + fleet
def test_serve_append_table_and_view_roundtrip():
    from cylon_tpu.serve import ServeEngine

    eng = ServeEngine()
    try:
        eng.register_table("t", _t())
        eng.register_view("agg", _gb_qf, GB_SPEC,
                          sources={"t": "t"})
        res = eng.append_table("t", _d(2, k0=3))
        assert res["generation"] == 2
        assert eng.read_view("agg")["lag"] == 1
        out = eng.refresh_view("agg")
        assert out["refreshed"] and not out["full_recompute"]
        got = eng.read_view("agg")
        assert got["lag"] == 0 and got["generations"] == {"t": 2}
        vs = eng.view_stats()["agg"]
        assert vs["generations"] == {"t": 2}
        # /tables reports the bumped version
        assert eng.table_stats()["t"]["version"]["generation"] == 2
    finally:
        eng.close()


def test_recover_restores_post_append_generation(tmp_path):
    """The ISSUE 18 fix satellite: a durable engine's append stamps
    the new generation into the catalog snapshot, and recover()
    restores THAT generation — not a silently re-aliased 1."""
    from cylon_tpu.serve import ServeEngine

    durable = str(tmp_path / "dur")
    eng = ServeEngine(durable_dir=durable)
    try:
        eng.register_table("t", _t())
        eng.append_table("t", _d(2, k0=8))
        eng.append_table("t", _d(1, k0=10))
    finally:
        eng.close()
    digest_before = catalog.table_version("t")["digest"]
    catalog.clear()

    eng2 = ServeEngine.recover(durable, replay=False)
    try:
        assert catalog.generation("t") == 3
        assert catalog.get_table("t").num_rows == 11
        assert catalog.table_version("t")["digest"] == digest_before
    finally:
        eng2.close()


def test_catalog_snapshot_generations_tolerate_pre_version_entries(
        tmp_path):
    from cylon_tpu.serve.durability import CatalogSnapshot

    snap = CatalogSnapshot(str(tmp_path))
    snap.save("old", _t())  # pre-versioning entry: no stamp
    snap.save("new", _t(), generation=5)
    assert snap.generations() == {"new": 5}


def test_fleet_snapshot_generations_reads_shared_store(tmp_path):
    from cylon_tpu.serve import fleet
    from cylon_tpu.serve.durability import CatalogSnapshot

    layout = fleet.FleetLayout(str(tmp_path))
    snap = CatalogSnapshot(layout.snapshot_dir)
    snap.save("tpch/lineitem", _t(), generation=4)
    assert fleet.snapshot_generations(str(tmp_path)) == {
        "tpch/lineitem": 4}


# ============================================= kill-mid-refresh chaos
V_DRIVER = '''
def run(resume_dir, out_path):
    import numpy as np
    import pandas as pd

    from cylon_tpu import catalog, views
    from cylon_tpu.table import Table

    catalog.clear()
    views.clear()
    rng = np.random.default_rng(7)
    catalog.put_table("t", Table.from_pydict({
        "k": rng.integers(0, 8, 400),
        "v": rng.normal(size=400)}))

    def qf(tables):
        df = tables["t"]
        g = df.groupby("k", as_index=False, sort=False)
        out = g.agg(s=("v", "sum"), n=("v", "size"))
        out["n"] = out["n"].astype(np.float64)
        return out

    views.register_view("agg", qf, {
        "merge": "groupby", "by": ["k"],
        "aggs": {"s": "sum", "n": "sum"}, "sort": ["k"]},
        sources={"t": "t"})
    catalog.append("t", pd.DataFrame({
        "k": rng.integers(0, 8, 120),
        "v": rng.normal(size=120)}))
    views.refresh("agg", resume_dir=resume_dir)
    r = views.read("agg")
    text = (r["result"].to_csv(index=False, float_format="%.17g")
            + r["digest"])
    if out_path:
        with open(out_path, "w") as f:
            f.write(text)
    return text
'''

V_CHILD = V_DRIVER + '''

if __name__ == "__main__":
    import os
    import sys

    import cylon_tpu  # noqa: F401
    from cylon_tpu import resilience, telemetry

    rdir, out_path = sys.argv[1:3]
    kill = os.environ.get("VIEW_KILL")
    if kill:
        point, nth = kill.rsplit(":", 1)
        resilience.install(resilience.FaultPlan(
            [resilience.FaultRule.kill(point, nth=int(nth))]))
    run(rdir or None, out_path or None)
    print(f"RESUMED={telemetry.total('ooc.units_resumed')}")
'''


def _child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH",
                                                         "")
    env.pop("VIEW_KILL", None)
    env.update(extra)
    return env


def test_kill_mid_refresh_resumes_byte_identical(tmp_path):
    """The ISSUE 18 acceptance chaos case: FaultRule.kill at the
    refresh's merge (global_merge hit 1 — registration consumed plan
    hit 1, the delta compute plan hit 2) dies AFTER the delta partial
    checkpointed (unit 0) and BEFORE the state swap; a fresh child
    resumes the unit and lands a view byte-identical (CSV + content
    digest) to a fault-free run, with the resident view never
    corrupted (the killed run published nothing)."""
    ns: dict = {}
    exec(V_DRIVER, ns)
    want = ns["run"](None, None)

    script = tmp_path / "view_child.py"
    script.write_text(V_CHILD)
    rdir, out = tmp_path / "ckpt", tmp_path / "out.txt"
    p1 = subprocess.run(
        [sys.executable, str(script), str(rdir), str(out)],
        env=_child_env(VIEW_KILL="global_merge:1"), cwd=str(REPO),
        capture_output=True, text=True, timeout=240)
    assert p1.returncode == KILL_EXIT_CODE, (
        f"kill child survived: rc={p1.returncode}\n{p1.stderr[-2000:]}")
    assert "injected HARD KILL" in p1.stderr
    manifest = json.loads((rdir / "manifest.json").read_text())
    assert len(manifest["completed"]) == 1  # delta yes, merge no
    assert not out.exists()

    p2 = subprocess.run(
        [sys.executable, str(script), str(rdir), str(out)],
        env=_child_env(), cwd=str(REPO), capture_output=True,
        text=True, timeout=240)
    assert p2.returncode == 0, p2.stderr[-2000:]
    resumed = int(p2.stdout.split("RESUMED=")[1].split()[0])
    assert resumed >= 1, "resume recomputed the delta from scratch"
    assert out.read_text() == want


def test_kill_before_delta_checkpoint_reruns_clean(tmp_path):
    """Kill at the delta compute itself (plan hit 2): nothing
    checkpointed, the rerun recomputes from zero and still matches the
    fault-free output exactly."""
    ns: dict = {}
    exec(V_DRIVER, ns)
    want = ns["run"](None, None)

    script = tmp_path / "view_child.py"
    script.write_text(V_CHILD)
    rdir, out = tmp_path / "ckpt", tmp_path / "out.txt"
    p1 = subprocess.run(
        [sys.executable, str(script), str(rdir), str(out)],
        env=_child_env(VIEW_KILL="plan:2"), cwd=str(REPO),
        capture_output=True, text=True, timeout=240)
    assert p1.returncode == KILL_EXIT_CODE, p1.stderr[-2000:]
    assert not out.exists()

    p2 = subprocess.run(
        [sys.executable, str(script), str(rdir), str(out)],
        env=_child_env(), cwd=str(REPO), capture_output=True,
        text=True, timeout=240)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert out.read_text() == want
