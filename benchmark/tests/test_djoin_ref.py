"""The distributed-join cell's generator and plain reference: the
reference join equals a ``pandas.merge`` of the same tables, its
per-key numbers equal those read off the merge, the check built on them
passes that merge and catches one altered payload and a wrong pairing
within a key, and the int32 control differs from it."""

import numpy as np
import pandas as pd
import pytest

import djoin_gen
import djoin_ref
import run

ROWS, WORKERS, DUP, PAYLOADS, DATA_SEED = 3000, 4, 0.9, 2**62, 0
N = djoin_gen.key_range(ROWS, WORKERS, DUP)
SEEDS = [0, 2**31 + 7, 12_345_678_901]


def _tables(seed, data_seed=DATA_SEED):
    return djoin_gen.generate(ROWS, WORKERS, DUP, PAYLOADS, data_seed, seed)


def _merge(data):
    return pd.DataFrame(data["left"]).merge(pd.DataFrame(data["right"]),
                                            on="key", how="inner")


def _sorted_rows(*cols):
    order = np.lexsort(cols[::-1])
    return [c[order] for c in cols]


@pytest.mark.parametrize("seed", SEEDS)
def test_generator_is_the_seeds(seed):
    a, b = _tables(seed), _tables(seed)
    other = _tables(seed + 1)
    for side in djoin_gen.SIDES:
        for c in ("key", "payload"):
            assert a[side][c].dtype == np.int64
            assert len(a[side][c]) == ROWS * WORKERS
            assert np.array_equal(a[side][c], b[side][c])
        # the keys are the data seed's, the payloads the run's
        assert np.array_equal(a[side]["key"], other[side]["key"])
        assert not np.array_equal(a[side]["key"],
                                  _tables(seed, DATA_SEED + 1)[side]["key"])
        assert not np.array_equal(a[side]["payload"], other[side]["payload"])
        assert a[side]["key"].min() >= 0 and a[side]["key"].max() < N
        assert a[side]["payload"].max() < PAYLOADS
        # payloads that int32 cannot hold
        assert a[side]["payload"].max() >= 2**40
    # each worker draws its own rows: no two blocks alike
    blocks = a["left"]["key"].reshape(WORKERS, ROWS)
    assert len({b.tobytes() for b in blocks}) == WORKERS


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_a_pandas_merge(seed):
    data = _tables(seed)
    merged = _merge(data)
    cols = [merged[c].to_numpy() for c in ("key", "payload_x", "payload_y")]
    rows = djoin_ref.join(data["left"], data["right"])
    for got, want in zip(_sorted_rows(*rows), _sorted_rows(*cols)):
        assert np.array_equal(got, want)
    want = djoin_ref.expected(data["left"], data["right"], N)
    assert int(want[0].sum()) == len(merged)
    observed, stray = djoin_ref.observed(*cols, N)
    assert stray == 0 and djoin_ref.wrong_keys(want, observed) == 0
    # the per-key numbers against pandas' own sums (small values: no wrap)
    small = {s: dict(data[s], payload=data[s]["payload"] >> 40)
             for s in djoin_gen.SIDES}
    m = _merge(small).assign(xy=lambda f: f.payload_x * f.payload_y)
    per = m.groupby("key").agg(rows=("key", "size"), x=("payload_x", "sum"),
                               y=("payload_y", "sum"), xy=("xy", "sum"))
    want = djoin_ref.expected(small["left"], small["right"], N)
    for got, col in zip(want, ("rows", "x", "y", "xy")):
        dense = np.zeros(N, np.int64)
        dense[per.index.to_numpy()] = per[col].to_numpy()
        assert np.array_equal(got, dense), col


def test_sums_are_exact_modulo_2_64():
    keys = np.array([0, 0, 1, 1, 1])
    values = np.array([2**63 - 1, 5, -(2**62), -(2**62), -(2**62)], np.int64)
    sums = djoin_ref._sums(keys, values, 3)
    with np.errstate(over="ignore"):
        want = [np.int64(2**63 - 1) + np.int64(5), np.int64(-(2**62)) * 3, 0]
    assert sums.tolist() == [int(w) for w in want]


def test_check_catches_an_altered_payload_a_wrong_pairing_and_the_control():
    driver = run.load_module("drivers", "dist_join_stream")
    data = _tables(5)
    k, x, y = (a.copy() for a in djoin_ref.join(data["left"], data["right"]))
    want = djoin_ref.expected(data["left"], data["right"], N)

    def checks(x, y, rows):
        got, stray = djoin_ref.observed(k, x, y, N)
        return driver._compare(want, rows, got, stray)

    n = len(k)
    assert checks(x, y, [n] * 3) == (3, 0, {
        "wrong_passes": (0, 0), "wrong_keys": (0, 0)})
    bad = x.copy()
    bad[17] += 1
    assert checks(bad, y, [n] * 3) == (3, 1, {
        "wrong_passes": (0, 0), "wrong_keys": (1, 0)})
    assert checks(bad, y, [n, n - 1, n])[1:] == (
        2, {"wrong_passes": (1, 0), "wrong_keys": (1, 0)})
    # two rows of one key trade their right payloads: every count and
    # payload sum stays, only the sum of products sees it
    i = next(i for i in range(n) for j in [np.flatnonzero(k == k[i])[0]]
             if x[i] != x[j] and y[i] != y[j])
    j = np.flatnonzero(k == k[i])[0]
    paired = y.copy()
    paired[[i, j]] = y[[j, i]]
    got, _ = djoin_ref.observed(k, x, paired, N)
    assert all(np.array_equal(w, g) for w, g in zip(want[:3], got[:3]))
    assert checks(x, paired, [n] * 3)[1:] == (
        1, {"wrong_passes": (0, 0), "wrong_keys": (1, 0)})
    # the control: the reference join in int32
    lower = djoin_ref.join(data["left"], data["right"], np.int32)
    assert len(lower[0]) == n
    got, stray = djoin_ref.observed(*lower, N)
    assert djoin_ref.wrong_keys(want, got, stray) > 0
