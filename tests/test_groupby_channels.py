"""The groupby's channel plan: each distinct per-group reduction runs
once, on both reduce paths, and answers stay pandas'.

``groupby._channel_plan`` keys a value sum by (source, accumulator
dtype) for sum, mean, var and std alike, counts in int32, and lets
columns without null flags share one valid-row count. The segment path
(CPU meshes; the TPU above ``SEGSCAN_MAX_ROWS``) and the scan path
(``CYLON_TPU_SEGSCAN=1`` forces it on the CPU) build every aggregate
from the same channels.
"""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import Table
from cylon_tpu.ops import groupby, groupby_aggregate

AGGS = [("v", "sum"), ("v", "mean"), ("v", "count"), ("v", "var"),
        ("w", "mean"), ("w", "size"), ("w", "min"), ("w", "sum"),
        ("w", "std"), ("v", "max"), ("w", "count")]


def _frame(rng, nulls):
    n = 120
    df = pd.DataFrame({"k": rng.integers(0, 7, n),
                       "v": rng.normal(size=n),
                       "w": rng.integers(-40, 40, n).astype(np.int64)})
    if nulls:
        df.loc[df.index % 5 == 0, "v"] = np.nan
        df.loc[df["k"] == 2, "v"] = np.nan    # one group all-null in v
        w = df["w"].astype("Int64")
        w[df.index % 3 == 1] = pd.NA
        df["w"] = w
    return df


def _groupby(df, mode, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_SEGSCAN", mode)
    return groupby_aggregate(Table.from_pandas(df), ["k"], AGGS,
                             out_capacity=64).to_pandas()


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("mode", ["0", "1"], ids=["segments", "scan"])
def test_repeated_sources_match_pandas_on_both_paths(mode, nulls, rng,
                                                     monkeypatch):
    df = _frame(rng, nulls)
    got = _groupby(df, mode, monkeypatch)
    want = df.groupby("k").agg(**{
        f"{src}_{op}": (src, op) for src, op in AGGS}).reset_index()
    pd.testing.assert_frame_equal(got, want.astype(got.dtypes.to_dict()),
                                  check_exact=False, rtol=1e-9)
    other = _groupby(df, "1" if mode == "0" else "0", monkeypatch)
    pd.testing.assert_frame_equal(got, other, check_exact=False,
                                  rtol=1e-12)


def _plan(df, aggs):
    t = Table.from_pandas(df)
    gid = jnp.zeros(t.capacity, jnp.int32)
    specs = [(src, op, f"{src}_{op}") for src, op in aggs]
    channels, _ = groupby._channel_plan(t, specs, gid, 1, 4, 0.5,
                                        None, None)
    return [(kind, val.dtype) for kind, val in channels]


@pytest.mark.parametrize("nulls,want", [
    # v: f64 sum (sum, mean, var, ...), its count, its sum of squares,
    # its max; w: f64 sum (mean, std), the valid-row count (size; w's
    # count and min when w has no nulls), min, i64 sum, sum of squares
    (False, 9),
    # w's nulls give w a count of its own beside size's
    (True, 10)], ids=["dense", "nulls"])
def test_each_distinct_channel_is_registered_once(nulls, want, rng):
    chans = _plan(_frame(rng, nulls), AGGS)
    assert len(chans) == want, chans
    sums = [dt for kind, dt in chans if kind == "sum"]
    # every count is int32; f64 sums: v, w (mean), and two sums of squares
    assert sums.count(jnp.dtype(jnp.float64)) == 4, chans
    assert sums.count(jnp.dtype(jnp.int64)) == 1, chans
    assert sums.count(jnp.dtype(jnp.int32)) == want - 7, chans


def test_an_aggregate_list_without_shared_work_keeps_its_channels(rng):
    """Nothing merges when no two aggregates share a reduction: one
    channel per sum, in its own accumulator dtype."""
    df = _frame(rng, False)
    assert _plan(df, [("v", "sum"), ("w", "sum")]) == [
        ("sum", jnp.dtype(jnp.float64)), ("sum", jnp.dtype(jnp.int64))]
