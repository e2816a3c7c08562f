"""One comparator for engine results against a pandas reference.

Used by the tests, the serve/fleet benches' correctness gates and
``chip_smoke.py``. Exact on keys, counts and strings; ``rtol`` on float
columns. Safe under pandas 3, whose default string dtype
(``StringDtype(na_value=nan)``) is neither a numpy dtype nor marks a
missing string with ``None``: a missing value equals a missing value
whatever its marker.
"""

import numpy as np
import pandas as pd


def _is_float(dtype) -> bool:
    return pd.api.types.is_float_dtype(dtype)


def _rel_err(g: np.ndarray, w: np.ndarray) -> float:
    g = np.asarray(g, np.float64)
    w = np.asarray(w, np.float64)
    both = np.isfinite(g) & np.isfinite(w)
    if not both.any():
        return 0.0
    g, w = g[both], w[both]
    den = np.where(w != 0, np.abs(w), 1.0)
    return float(np.max(np.abs(g - w) / den))


def compare_results(got, want, *, rtol: float = 1e-9,
                    ignore_order: bool = False, float_cols=None):
    """``(diff, max_rel_err)``: ``diff`` is None when ``got`` matches
    ``want`` and otherwise names the first difference; ``max_rel_err``
    is the largest relative error over the float values compared.

    Frames must have the same columns and length. ``ignore_order``
    sorts both by their exact columns, then by their float columns.
    ``float_cols`` names the columns held to
    ``rtol`` (default: the float-typed columns of ``want``); every
    other column must match exactly. Scalars and arrays compare
    numerically at ``rtol``.
    """
    if not isinstance(want, pd.DataFrame):
        g = np.asarray(got, np.float64)
        w = np.asarray(want, np.float64)
        err = _rel_err(g, w) if g.shape == w.shape else float("inf")
        ok = g.shape == w.shape and bool(
            np.isclose(g, w, rtol=rtol, atol=0.0, equal_nan=True).all())
        return (None if ok else f"value {got!r} != {want!r}"), err
    if list(got.columns) != list(want.columns):
        return f"columns {list(got.columns)} != {list(want.columns)}", 0.0
    if len(got) != len(want):
        return f"{len(got)} rows != {len(want)} rows", 0.0
    fcols = ([c for c in want.columns if _is_float(want[c].dtype)]
             if float_cols is None else list(float_cols))
    if ignore_order and len(want):
        # exact columns first; floats break ties between equal keys
        keys = [c for c in want.columns if c not in fcols] + fcols
        got = got.sort_values(keys, kind="stable")
        want = want.sort_values(keys, kind="stable")
    got = got.reset_index(drop=True)
    want = want.reset_index(drop=True)
    worst, diff = 0.0, None
    for c in want.columns:
        g, w = got[c], want[c]
        if c in fcols:
            gv = g.to_numpy(np.float64, na_value=np.nan)
            wv = w.to_numpy(np.float64, na_value=np.nan)
            worst = max(worst, _rel_err(gv, wv))
            close = np.isclose(gv, wv, rtol=rtol, atol=0.0, equal_nan=True)
            if diff is None and not close.all():
                i = int(np.argmin(close))
                diff = f"column {c!r} row {i}: {gv[i]!r} != {wv[i]!r}"
            continue
        gna, wna = pd.isna(g).to_numpy(), pd.isna(w).to_numpy()
        if diff is None and (gna != wna).any():
            i = int(np.argmax(gna != wna))
            diff = f"column {c!r} row {i}: missing in one side only"
        elif diff is None:
            gl, wl = list(g[~gna]), list(w[~wna])
            if gl != wl:
                i = next(j for j, (a, b) in enumerate(zip(gl, wl))
                         if a != b)
                diff = f"column {c!r} value {i}: {gl[i]!r} != {wl[i]!r}"
    return diff, worst


def assert_results_match(got, want, **kw) -> float:
    """Raise AssertionError on the first difference (see
    :func:`compare_results`); return the max relative float error."""
    diff, err = compare_results(got, want, **kw)
    assert diff is None, diff
    return err
