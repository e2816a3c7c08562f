"""Local relational ops vs the pandas oracle.

Mirrors the reference's python test strategy (``python/test/test_rl.py``,
``test_frame.py``): compute with the framework, compare against pandas on
the same data. Join golden behavior mirrors ``cpp/test/join_test.cpp``.
"""

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import Table
from cylon_tpu.errors import OutOfCapacity
from cylon_tpu.ops import (
    filter_table, head, join, sort_table, take, unique, union, intersect,
    subtract, concat_tables, groupby_aggregate, table_aggregate,
    equal_tables, sample,
)


def _df_eq_unordered(got: pd.DataFrame, want: pd.DataFrame):
    got = got.sort_values(list(got.columns)).reset_index(drop=True)
    want = want.sort_values(list(want.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got.astype(want.dtypes.to_dict()), want,
                                  check_dtype=False)


# ---------------------------------------------------------------- joins
JOIN_HOWS = ["inner", "left", "right", "outer"]


@pytest.mark.parametrize("how", JOIN_HOWS)
def test_join_int_keys_vs_pandas(how, rng):
    ldf = pd.DataFrame({"k": rng.integers(0, 20, 50),
                        "a": rng.normal(size=50)})
    rdf = pd.DataFrame({"k": rng.integers(0, 20, 40),
                        "b": rng.normal(size=40)})
    want = ldf.merge(rdf, on="k", how=how)
    out_cap = len(ldf) * len(rdf)
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf), on="k",
               how=how, out_capacity=out_cap).to_pandas()
    assert len(got) == len(want)
    _df_eq_unordered(got[["k", "a", "b"]], want[["k", "a", "b"]])


@pytest.mark.parametrize("how", JOIN_HOWS)
def test_join_multi_key(how, rng):
    ldf = pd.DataFrame({"k1": rng.integers(0, 5, 30),
                        "k2": rng.integers(0, 4, 30),
                        "a": np.arange(30)})
    rdf = pd.DataFrame({"k1": rng.integers(0, 5, 25),
                        "k2": rng.integers(0, 4, 25),
                        "b": np.arange(25) * 10})
    want = ldf.merge(rdf, on=["k1", "k2"], how=how)
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf),
               on=["k1", "k2"], how=how, out_capacity=2000).to_pandas()
    assert len(got) == len(want)
    _df_eq_unordered(got, want)


def test_join_string_keys(rng):
    ldf = pd.DataFrame({"k": ["apple", "fig", "pear", "apple"],
                        "a": [1, 2, 3, 4]})
    rdf = pd.DataFrame({"k": ["pear", "apple", "kiwi"],
                        "b": [10, 20, 30]})
    want = ldf.merge(rdf, on="k", how="inner")
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf),
               on="k", how="inner").to_pandas()
    _df_eq_unordered(got, want)


def test_join_different_key_names_and_suffixes(rng):
    ldf = pd.DataFrame({"lk": [1, 2, 3], "v": [1.0, 2.0, 3.0]})
    rdf = pd.DataFrame({"rk": [2, 3, 4], "v": [20.0, 30.0, 40.0]})
    want = ldf.merge(rdf, left_on="lk", right_on="rk", how="inner")
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf),
               left_on="lk", right_on="rk", how="inner").to_pandas()
    assert sorted(got.columns) == sorted(want.columns)  # v_x, v_y
    _df_eq_unordered(got, want)


def test_join_empty_result():
    l = Table.from_pydict({"k": [1, 2], "a": [1, 2]})
    r = Table.from_pydict({"k": [5, 6], "b": [1, 2]})
    assert join(l, r, on="k", how="inner").num_rows == 0
    assert join(l, r, on="k", how="left").num_rows == 2
    assert join(l, r, on="k", how="outer").num_rows == 4


def test_join_overflow_detected():
    l = Table.from_pydict({"k": [1] * 8, "a": range(8)})
    r = Table.from_pydict({"k": [1] * 8, "b": range(8)})
    t = join(l, r, on="k", how="inner", out_capacity=10)  # needs 64
    with pytest.raises(OutOfCapacity):
        t.num_rows


def test_join_nan_keys_match_pandas():
    # pandas merges NaN keys with NaN keys
    ldf = pd.DataFrame({"k": [1.0, np.nan, 3.0], "a": [1, 2, 3]})
    rdf = pd.DataFrame({"k": [np.nan, 3.0], "b": [10, 20]})
    want = ldf.merge(rdf, on="k", how="inner")
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf),
               on="k", how="inner").to_pandas()
    assert len(got) == len(want) == 2


# ------------------------------------------------------------- sort/filter
def test_sort_single_and_multi(rng):
    df = pd.DataFrame({"a": rng.integers(0, 10, 40),
                       "b": rng.normal(size=40)})
    t = Table.from_pandas(df)
    got = sort_table(t, ["a", "b"]).to_pandas()
    want = df.sort_values(["a", "b"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)

    got = sort_table(t, ["a", "b"], ascending=[True, False]).to_pandas()
    want = df.sort_values(["a", "b"], ascending=[True, False]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)


def test_sort_nan_last():
    df = pd.DataFrame({"a": [3.0, np.nan, 1.0, 2.0]})
    got = sort_table(Table.from_pandas(df), ["a"]).to_pandas()
    want = df.sort_values("a").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)
    got = sort_table(Table.from_pandas(df), ["a"], ascending=False).to_pandas()
    want = df.sort_values("a", ascending=False).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)


def test_sort_strings():
    df = pd.DataFrame({"s": ["pear", "apple", "fig"], "v": [1, 2, 3]})
    got = sort_table(Table.from_pandas(df), ["s"]).to_pandas()
    want = df.sort_values("s").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)


def test_filter_and_take(rng):
    df = pd.DataFrame({"a": np.arange(20), "b": np.arange(20) * 2.0})
    t = Table.from_pandas(df)
    mask = t.column("a").data % 3 == 0
    got = filter_table(t, mask).to_pandas()
    want = df[df["a"] % 3 == 0].reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)

    idx = np.array([5, 1, 7], dtype=np.int32)
    got = take(t, idx).to_pandas()
    pd.testing.assert_frame_equal(got, df.iloc[idx].reset_index(drop=True))


def test_head_and_sample():
    t = Table.from_pydict({"a": list(range(10))})
    assert head(t, 3).to_pydict() == {"a": [0, 1, 2]}
    s = sample(t, 4)
    assert s.num_rows == 4
    assert all(0 <= v < 10 for v in s.to_pydict()["a"])


def test_concat(rng):
    d1 = pd.DataFrame({"a": [1, 2], "s": ["x", "q"]})
    d2 = pd.DataFrame({"a": [3], "s": ["z"]})
    got = concat_tables([Table.from_pandas(d1), Table.from_pandas(d2)]).to_pandas()
    want = pd.concat([d1, d2]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)


# --------------------------------------------------------------- set ops
def test_unique_vs_pandas(rng):
    df = pd.DataFrame({"a": rng.integers(0, 5, 30),
                       "b": rng.integers(0, 3, 30)})
    got = unique(Table.from_pandas(df)).to_pandas()
    want = df.drop_duplicates().reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want)  # order preserved


def test_union_intersect_subtract():
    a = pd.DataFrame({"x": [1, 2, 2, 3], "y": ["a", "b", "b", "c"]})
    b = pd.DataFrame({"x": [2, 3, 4], "y": ["b", "zz", "d"]})
    ta, tb = Table.from_pandas(a), Table.from_pandas(b)

    got = union(ta, tb).to_pandas()
    want = pd.concat([a, b]).drop_duplicates().reset_index(drop=True)
    _df_eq_unordered(got, want)

    got = intersect(ta, tb).to_pandas()
    want = a.merge(b, on=["x", "y"]).drop_duplicates().reset_index(drop=True)
    _df_eq_unordered(got, want)

    got = subtract(ta, tb).to_pandas()
    mark = a.merge(b, on=["x", "y"], how="left", indicator=True)
    want = mark[mark["_merge"] == "left_only"][["x", "y"]].drop_duplicates() \
        .reset_index(drop=True)
    _df_eq_unordered(got, want)


def test_equal_tables():
    a = Table.from_pydict({"x": [1, 2, 3]})
    b = Table.from_pydict({"x": [3, 2, 1]})
    assert equal_tables(a, b)
    assert not equal_tables(a, b, ordered=True)
    assert not equal_tables(a, Table.from_pydict({"x": [1, 2, 4]}))


# --------------------------------------------------------------- groupby
def test_groupby_basic_vs_pandas(rng):
    df = pd.DataFrame({"k": rng.integers(0, 7, 60),
                       "v": rng.normal(size=60),
                       "w": rng.integers(0, 100, 60)})
    t = Table.from_pandas(df)
    got = groupby_aggregate(t, ["k"], [("v", "sum"), ("v", "mean"),
                                       ("w", "min"), ("w", "max"),
                                       ("v", "count")]).to_pandas()
    want = df.groupby("k").agg(
        v_sum=("v", "sum"), v_mean=("v", "mean"), w_min=("w", "min"),
        w_max=("w", "max"), v_count=("v", "count")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_groupby_var_std_nunique_median(rng):
    df = pd.DataFrame({"k": rng.integers(0, 5, 50),
                       "v": rng.normal(size=50)})
    t = Table.from_pandas(df)
    got = groupby_aggregate(t, ["k"], [("v", "var"), ("v", "std"),
                                       ("v", "nunique"), ("v", "median")]
                            ).to_pandas()
    want = df.groupby("k").agg(
        v_var=("v", "var"), v_std=("v", "std"), v_nunique=("v", "nunique"),
        v_median=("v", "median")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_groupby_multi_key_and_strings(rng):
    df = pd.DataFrame({"k1": rng.choice(["a", "b", "c"], 40),
                       "k2": rng.integers(0, 3, 40),
                       "v": rng.integers(0, 10, 40)})
    t = Table.from_pandas(df)
    got = groupby_aggregate(t, ["k1", "k2"], [("v", "sum")]).to_pandas()
    want = df.groupby(["k1", "k2"]).agg(v_sum=("v", "sum")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_groupby_nan_values_skipped():
    df = pd.DataFrame({"k": [1, 1, 2, 2],
                       "v": [1.0, np.nan, 3.0, 4.0]})
    t = Table.from_pandas(df)
    got = groupby_aggregate(t, ["k"], [("v", "sum"), ("v", "count"),
                                       ("v", "size")]).to_pandas()
    assert got["v_sum"].tolist() == [1.0, 7.0]
    assert got["v_count"].tolist() == [1, 2]
    assert got["v_size"].tolist() == [2, 2]


def test_groupby_first_last():
    df = pd.DataFrame({"k": [1, 1, 2], "v": [10, 20, 30]})
    got = groupby_aggregate(Table.from_pandas(df), ["k"],
                            [("v", "first"), ("v", "last")]).to_pandas()
    assert got["v_first"].tolist() == [10, 30]
    assert got["v_last"].tolist() == [20, 30]


# ----------------------------------------------------------- aggregates
def test_table_aggregates(rng):
    df = pd.DataFrame({"v": rng.normal(size=100)})
    t = Table.from_pandas(df)
    assert np.isclose(float(table_aggregate(t, "v", "sum")), df["v"].sum())
    assert np.isclose(float(table_aggregate(t, "v", "mean")), df["v"].mean())
    assert np.isclose(float(table_aggregate(t, "v", "var")), df["v"].var())
    assert np.isclose(float(table_aggregate(t, "v", "std")), df["v"].std())
    assert float(table_aggregate(t, "v", "min")) == df["v"].min()
    assert float(table_aggregate(t, "v", "max")) == df["v"].max()
    assert int(table_aggregate(t, "v", "count")) == 100
    assert int(table_aggregate(t, "v", "nunique")) == df["v"].nunique()


def test_aggregate_skips_nan():
    t = Table.from_pydict({"v": [1.0, np.nan, 3.0]})
    assert float(table_aggregate(t, "v", "sum")) == 4.0
    assert int(table_aggregate(t, "v", "count")) == 2


# -------------------------------------------------- padded-table behavior
def test_ops_respect_padding(rng):
    """All ops must ignore rows beyond nrows."""
    df = pd.DataFrame({"k": [3, 1, 2], "v": [30.0, 10.0, 20.0]})
    t = Table.from_pandas(df, capacity=16)  # 13 garbage-padding rows
    assert sort_table(t, ["k"]).to_pandas()["k"].tolist() == [1, 2, 3]
    assert unique(t).num_rows == 3
    g = groupby_aggregate(t, ["k"], [("v", "sum")])
    assert g.num_rows == 3
    j = join(t, t, on="k", how="inner", suffixes=("_l", "_r"))
    assert j.num_rows == 3
    assert int(table_aggregate(t, "v", "count")) == 3


# ----------------------------------------- review-finding regressions
def test_null_payloads_group_together_after_outer_join():
    """Nulls injected by outer joins must compare equal regardless of
    underlying payload bytes."""
    l = Table.from_pydict({"k": [1, 2, 3]})
    r = pd.DataFrame({"k": [1, 2], "b": pd.array([7, None], dtype="Int64")})
    j = join(l, Table.from_pandas(r), on="k", how="left")
    g = groupby_aggregate(j, ["b"], [("k", "count")])
    # pandas: groups are {7: 1, null: 2}
    assert g.num_rows == 2
    counts = sorted(g.to_pandas()["k_count"].tolist())
    assert counts == [1, 2]


def test_fullouter_string_keys():
    ldf = pd.DataFrame({"k": ["a", "b"], "v": [1, 2]})
    rdf = pd.DataFrame({"k": ["b", "c"], "w": [10, 20]})
    got = join(Table.from_pandas(ldf), Table.from_pandas(rdf),
               on="k", how="outer").to_pandas()
    want = ldf.merge(rdf, on="k", how="outer")
    _df_eq_unordered(got, want)


def test_setops_overflow_detected():
    a = Table.from_pydict({"x": [1, 2, 3, 4, 5]})
    b = Table.from_pydict({"x": [6, 7, 8, 9, 10]})
    u = union(a, b, out_capacity=8)  # needs 10
    with pytest.raises(OutOfCapacity):
        u.num_rows
    u2 = union(a, b, out_capacity=16)
    assert u2.num_rows == 10


def test_equal_tables_multiset():
    a = Table.from_pydict({"x": [1, 1, 2]})
    b = Table.from_pydict({"x": [1, 2, 2]})
    assert not equal_tables(a, b)
    assert equal_tables(a, Table.from_pydict({"x": [2, 1, 1]}))


def test_f64_bits_matches_bitcast(rng):
    """f64_bits (the TPU software path) must be bit-identical to the
    real bitcast for every value class."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu.ops.kernels import f64_bits

    nasty = np.array([
        0.0, -0.0, 1.0, -1.0, 1.5, -2.25, np.pi, -np.e,
        np.inf, -np.inf, np.nan,
        np.finfo(np.float64).max, np.finfo(np.float64).min,
        np.finfo(np.float64).tiny,          # smallest normal
        2.0**52, 2.0**52 + 1, 2.0**53, 2.0**-1022, 2.0**1023,
        1 + 2.0**-52,                       # mantissa LSB
    ])
    vals = np.concatenate([nasty, rng.normal(size=500),
                           rng.normal(size=500) * 1e300,
                           rng.normal(size=500) * 1e-300])
    x = jnp.asarray(vals)
    got = np.asarray(f64_bits(x))
    want = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint64))
    np.testing.assert_array_equal(got, want)
    # subnormal inputs: XLA arithmetic is DAZ, so the software path maps
    # them to signed zero — the same value every arithmetic op sees
    subs = jnp.asarray(np.array([5e-324, -5e-324, 1e-310, -3.1e-320]))
    got = np.asarray(f64_bits(subs))
    np.testing.assert_array_equal(
        got, np.array([0, 1 << 63, 0, 1 << 63], np.uint64))


def test_fullouter_join_content_equal_dictionaries():
    """Independently ingested tables over the same string value set have
    content-equal but distinct Dictionary objects; outer-join key
    coalescing must accept them (content equality, not identity)."""
    from cylon_tpu import Table
    from cylon_tpu.ops.join import join

    a = Table.from_pydict({"k": ["x", "y"], "v": [1, 2]})
    b = Table.from_pydict({"k": ["y", "x"], "w": [3, 4]})
    d1, d2 = a.column("k").dictionary, b.column("k").dictionary
    assert d1 is not d2 and d1 == d2  # the content-equal pass-through
    out = join(a, b, on="k", how="fullouter", out_capacity=8).to_pandas()
    got = out.sort_values("k").reset_index(drop=True)
    assert got["k"].tolist() == ["x", "y"]
    assert got["v"].tolist() == [1, 2] and got["w"].tolist() == [4, 3]


def test_sort_nulls_keep_original_order():
    """pandas sort_values keeps null rows in ORIGINAL order (stable);
    null slots carry arbitrary payload bytes, so the sort key must be
    zeroed under nulls — ordering by garbage would be nondeterministic."""
    import jax.numpy as jnp

    from cylon_tpu import Table, dtypes
    from cylon_tpu.column import Column
    from cylon_tpu.ops.selection import sort_table

    data = jnp.asarray([5, 9, 1, 7, 3, 2], jnp.int64)
    validity = jnp.asarray([False, True, False, True, False, True])
    k = jnp.arange(6, dtype=jnp.int64)
    t = Table({"v": Column(data, validity, dtypes.int64),
               "k": Column(k, None, dtypes.int64)}, 6)
    out = sort_table(t, ["v"]).to_pandas()
    # valid ascending first (2, 7, 9 -> k 5,3,1), then nulls in
    # original row order (k 0,2,4)
    assert out["k"].tolist() == [5, 3, 1, 0, 2, 4]


def test_multidim_columns_through_payload_paths():
    """2-D (embedding-like) columns can't ride lax.sort payloads; they
    take the original-index gather fallback in columns_to_payloads —
    exercise filter, sort, unique and groupby over such a table."""
    import jax.numpy as jnp
    import numpy as np

    from cylon_tpu import Table, dtypes
    from cylon_tpu.column import Column
    from cylon_tpu.ops.groupby import groupby_aggregate
    from cylon_tpu.ops.selection import filter_table, sort_table
    from cylon_tpu.ops.setops import unique

    k = jnp.asarray([3, 1, 3, 2, 1, 2], jnp.int64)
    emb = jnp.asarray(np.arange(12, dtype=np.float32).reshape(6, 2))
    t = Table({"k": Column(k, None, dtypes.int64),
               "e": Column(emb, None, dtypes.float32)}, 6)

    f = filter_table(t, jnp.asarray([True, False, True, True, False,
                                     True]))
    assert f.num_rows == 4
    np.testing.assert_array_equal(np.asarray(f.column("e").data[:4]),
                                  np.asarray(emb)[[0, 2, 3, 5]])

    s = sort_table(t, ["k"])
    np.testing.assert_array_equal(np.asarray(s.column("k").data[:6]),
                                  [1, 1, 2, 2, 3, 3])
    # stable: equal keys keep original order, embeddings follow rows
    np.testing.assert_array_equal(np.asarray(s.column("e").data[:6]),
                                  np.asarray(emb)[[1, 4, 3, 5, 0, 2]])

    u = unique(t, ["k"])
    assert u.num_rows == 3
    np.testing.assert_array_equal(np.asarray(u.column("k").data[:3]),
                                  [3, 1, 2])  # first occurrences, row order
    np.testing.assert_array_equal(np.asarray(u.column("e").data[:3]),
                                  np.asarray(emb)[[0, 1, 3]])

    g = groupby_aggregate(t, ["k"], [("e", "first", "e0"),
                                     ("e", "sum", "es")],
                          out_capacity=4)
    assert g.num_rows == 3
    np.testing.assert_array_equal(np.asarray(g.column("e0").data[:3]),
                                  np.asarray(emb)[[1, 3, 0]])  # key-sorted
    want = np.stack([np.asarray(emb)[[1, 4]].sum(0),
                     np.asarray(emb)[[3, 5]].sum(0),
                     np.asarray(emb)[[0, 2]].sum(0)])
    np.testing.assert_allclose(np.asarray(g.column("es").data[:3]), want)

    g2 = groupby_aggregate(t, ["k"], [("e", "mean", "em")],
                           out_capacity=4)
    np.testing.assert_allclose(np.asarray(g2.column("em").data[:3]),
                               want / 2.0)
    # out_capacity == trailing dim: the shapes coincide, the axis must
    # not (regression for a silent wrong-axis broadcast)
    g3 = groupby_aggregate(t, ["k"], [("e", "mean", "em")],
                           out_capacity=2)
    np.testing.assert_allclose(np.asarray(g3.column("em").data[:2]),
                               (want / 2.0)[:2])


def test_all_join_types_exact_pandas_order(rng):
    """Exact output-order parity for every join type, including
    how="outer" where pandas sorts the key union lexicographically
    (regression: the order restore used to emit left-frame order with
    extras appended, not the sorted union)."""
    n = 800
    l = Table.from_pydict({"k": rng.integers(0, 40, n).astype(np.int64),
                           "a": rng.normal(size=n)})
    r = Table.from_pydict({"k": rng.integers(0, 800, n).astype(np.int64),
                           "b": rng.normal(size=n)})
    lp, rp = l.to_pandas(), r.to_pandas()
    for how in ("inner", "left", "right", "outer"):
        got = join(l, r, on="k", how=how,
                   out_capacity=40_000).to_pandas()
        exp = lp.merge(rp, on="k", how=how)
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      exp.reset_index(drop=True),
                                      check_dtype=False)


def test_outer_join_null_keys_sort_last():
    """pandas sorts null keys LAST in the outer key union (regression:
    group_sort ranks null groups among zeroed values, which put them
    first for string keys)."""
    l = Table.from_pandas(pd.DataFrame({"k": ["b", None, "a"],
                                        "x": [1.0, 2.0, 3.0]}))
    r = Table.from_pandas(pd.DataFrame({"k": [None, "c", "b"],
                                        "y": [10.0, 20.0, 30.0]}))
    got = join(l, r, on="k", how="outer").to_pandas()
    exp = l.to_pandas().merge(r.to_pandas(), on="k", how="outer")
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True),
                                  check_dtype=False)


def test_outer_join_multikey_null_order():
    """Multi-key outer join: pandas sorts the key union lexicographically
    with nulls last PER LEVEL — a (a, None) row belongs inside the
    k1=a run, not after all non-null groups."""
    l = Table.from_pandas(pd.DataFrame(
        {"k1": ["a", "a", "b"], "k2": [None, "z", "c"],
         "x": [1.0, 2.0, 3.0]}))
    r = Table.from_pandas(pd.DataFrame(
        {"k1": ["b", "a"], "k2": ["c", None], "y": [10.0, 20.0]}))
    got = join(l, r, on=["k1", "k2"], how="outer").to_pandas()
    exp = l.to_pandas().merge(r.to_pandas(), on=["k1", "k2"], how="outer")
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True),
                                  check_dtype=False)


def test_groupby_segscan_path_parity(rng, monkeypatch):
    """The TPU segmented-scan + compaction aggregation path
    (kernels.segmented_totals; CYLON_TPU_SEGSCAN=1 forces it on the CPU
    mesh) must match the segment-op path bit-for-bit on every aggregate
    family, including out_capacity larger than the row count, all-null
    groups, first/last, and nunique/median."""
    monkeypatch.setenv("CYLON_TPU_SEGSCAN", "1")
    df = pd.DataFrame({"k": rng.integers(0, 9, 80),
                       "v": rng.normal(size=80),
                       "w": rng.integers(-50, 50, 80).astype(np.int64)})
    df.loc[df.index % 7 == 0, "v"] = np.nan
    df.loc[df["k"] == 3, "v"] = np.nan    # one group entirely null
    t = Table.from_pandas(df)
    aggs = [("v", "sum"), ("v", "count"), ("v", "size"), ("v", "mean"),
            ("v", "var"), ("v", "std"), ("w", "min"), ("w", "max"),
            ("v", "first"), ("v", "last"), ("w", "nunique"),
            ("v", "median")]
    got = groupby_aggregate(t, ["k"], aggs,
                            out_capacity=200).to_pandas()  # > nrows
    want = df.groupby("k").agg(
        v_sum=("v", "sum"), v_count=("v", "count"), v_size=("v", "size"),
        v_mean=("v", "mean"), v_var=("v", "var"), v_std=("v", "std"),
        w_min=("w", "min"), w_max=("w", "max"), v_first=("v", "first"),
        v_last=("v", "last"), w_nunique=("w", "nunique"),
        v_median=("v", "median")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    monkeypatch.setenv("CYLON_TPU_SEGSCAN", "0")
    got_seg = groupby_aggregate(t, ["k"], aggs,
                                out_capacity=200).to_pandas()
    pd.testing.assert_frame_equal(got, got_seg)


@pytest.mark.parametrize("platform,rows,wide", [
    ("cpu", 1000, False), ("cpu", 4_000_000, True),
    ("tpu", 1000, True), ("tpu", 4_000_000, True)])
def test_wide_payloads_leave_the_sort_on_tpu_at_any_size(platform, rows,
                                                          wide):
    """Off the TPU wide payloads ride the sort below the row knee; on
    the TPU a wide sort's compile time rules at every size."""
    from cylon_tpu.ops.selection import use_gather_path
    from cylon_tpu.platform import on_platform

    with on_platform(platform):
        assert use_gather_path(20, rows) is wide
        assert not use_gather_path(4, rows)


@pytest.mark.parametrize("platform,cols,gather", [
    ("cpu", ("f64", "f64"), False), ("tpu", ("f64",), False),
    ("tpu", ("f64", "f64"), True), ("tpu", ("i32", "i32", "i32"), True)])
def test_tpu_group_sort_carries_one_64bit_value_column(platform, cols,
                                                       gather):
    """On the TPU the group sort carries at most one 64-bit value
    column (each payload word costs compile time); wider value sets
    are gathered through the sorted row index. Off the TPU the shared
    width-and-rows knee still decides."""
    from cylon_tpu.column import Column
    from cylon_tpu.ops.groupby import _gather_values
    from cylon_tpu.platform import on_platform

    dt = {"f64": np.float64, "i32": np.int32}
    src = {f"c{i}": Column.from_numpy(np.zeros(8, dt[c]))
           for i, c in enumerate(cols)}
    with on_platform(platform):
        assert _gather_values(src, 1000) is gather
