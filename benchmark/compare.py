"""The comparisons that decide ``correct``.

``compare_frames`` is a copy of the engine's comparator
(``cylon_tpu/testing.py``), kept here so that no change to the program
can loosen it: exact on keys, counts and strings, a relative tolerance
on floats, and the largest relative float error reported either way.
"""

import numpy as np
import pandas as pd


def _rel_err(g, w):
    g = np.asarray(g, np.float64)
    w = np.asarray(w, np.float64)
    both = np.isfinite(g) & np.isfinite(w)
    if not both.any():
        return 0.0
    g, w = g[both], w[both]
    den = np.where(w != 0, np.abs(w), 1.0)
    return float(np.max(np.abs(g - w) / den))


def compare_frames(got, want, rtol):
    """``(diff, max_rel_err)``: ``diff`` is None when ``got`` matches
    ``want`` and otherwise names the first difference. Frames must have
    the same columns, length and order; floats are held to ``rtol``,
    every other column must be equal. Scalars compare at ``rtol``."""
    if not isinstance(want, pd.DataFrame):
        g = np.asarray(got, np.float64)
        w = np.asarray(want, np.float64)
        if g.shape != w.shape:
            return f"value {got!r} != {want!r}", float("inf")
        err = _rel_err(g, w)
        ok = bool(np.isclose(g, w, rtol=rtol, atol=0.0,
                             equal_nan=True).all())
        return (None if ok else f"value {got!r} != {want!r}"), err
    if not isinstance(got, pd.DataFrame):
        return f"a {type(got).__name__} where a frame was due", float("inf")
    if list(got.columns) != list(want.columns):
        return (f"columns {list(got.columns)} != {list(want.columns)}",
                float("inf"))
    if len(got) != len(want):
        return f"{len(got)} rows != {len(want)} rows", float("inf")
    got = got.reset_index(drop=True)
    want = want.reset_index(drop=True)
    worst, diff = 0.0, None
    for c in want.columns:
        g, w = got[c], want[c]
        if pd.api.types.is_float_dtype(w.dtype):
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

