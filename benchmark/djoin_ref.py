"""The plain reference of an inner equi-join on one int64 key.

``join`` is the join itself: every pair of a left and a right row with
one key, expanded on the host. The check reads a join's output per key
as four numbers: its row count, the sums of its left payloads and of
its right payloads, and the sum of the products of each row's left and
right payload. For a key with ``cl`` rows on the left and ``cr`` on the
right, whose payloads sum to ``sl`` and ``sr``, the inner join holds
``cl * cr`` rows, and those sums are ``sl * cr``, ``sr * cl`` and
``sl * sr``. Every sum and product is taken exactly modulo 2**64, as
int64 arithmetic wraps. The products tie each left payload to its right
partners: a join that pairs the rows of one key wrongly keeps the first
three numbers and changes the fourth, unless its wrong pairs happen to
give the same sum of products modulo 2**64, which payloads drawn
uniformly from 62 bits make vanishingly unlikely. Nothing here imports
the engine.
"""

import numpy as np


def _sums(keys, values, n):
    """Per key of ``[0, n)``, the sum of the int64 ``values`` modulo
    2**64, exactly: ``np.add.at`` in uint64, which wraps."""
    total = np.zeros(n, np.uint64)
    np.add.at(total, keys, values.astype(np.int64).view(np.uint64))
    return total.view(np.int64)


def expected(left, right, n):
    """``(rows, left sum, right sum, product sum)`` per key of
    ``[0, n)`` of the inner join of ``left`` and ``right`` (each
    ``{"key", "payload"}``), as int64 arrays, from each side's counts
    and sums."""
    lk, rk = left["key"], right["key"]
    cl = np.bincount(lk, minlength=n).astype(np.int64)
    cr = np.bincount(rk, minlength=n).astype(np.int64)
    sl = _sums(lk, left["payload"], n)
    sr = _sums(rk, right["payload"], n)
    return cl * cr, sl * cr, sr * cl, sl * sr


def join(left, right, dtype=np.int64):
    """The inner join's rows ``(key, left payload, right payload)`` as
    int64 arrays, key by key. With a narrower ``dtype`` every column is
    cast to it first, as a join computed in that type would hold it:
    the control's lower precision."""
    lk, lx, rk, ry = (a.astype(dtype).astype(np.int64) for a in
                      (left["key"], left["payload"], right["key"],
                       right["payload"]))
    lo, ro = np.argsort(lk, kind="stable"), np.argsort(rk, kind="stable")
    lk, lx, rk, ry = lk[lo], lx[lo], rk[ro], ry[ro]
    lkeys, lstart, lcount = np.unique(lk, return_index=True,
                                      return_counts=True)
    rkeys, rstart, rcount = np.unique(rk, return_index=True,
                                      return_counts=True)
    _, li, ri = np.intersect1d(lkeys, rkeys, assume_unique=True,
                               return_indices=True)
    cl, cr = lcount[li], rcount[ri]
    per = cl * cr
    group = np.repeat(np.arange(len(per)), per)
    within = np.arange(int(per.sum())) - np.repeat(np.cumsum(per) - per,
                                                   per)
    lrow = lstart[li][group] + within // cr[group]
    rrow = rstart[ri][group] + within % cr[group]
    return lk[lrow], lx[lrow], ry[rrow]


def observed(keys, left_payload, right_payload, n):
    """The same four numbers per key of ``[0, n)`` read off a join's
    output rows, and the number of rows whose key lies outside that
    range."""
    inside = (keys >= 0) & (keys < n)
    k, x, y = keys[inside], left_payload[inside], right_payload[inside]
    per = (np.bincount(k, minlength=n).astype(np.int64),
           _sums(k, x, n), _sums(k, y, n), _sums(k, x * y, n))
    return per, int(np.count_nonzero(~inside))


def wrong_keys(want, got, stray=0):
    """Keys whose four numbers differ in any, plus the rows with a
    stray key."""
    differ = np.zeros(len(want[0]), bool)
    for w, g in zip(want, got):
        differ |= w != g
    return int(np.count_nonzero(differ)) + stray
