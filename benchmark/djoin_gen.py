"""The two tables of Cylon's distributed-join benchmark, made from two seeds.

Cylon's ``cpp/src/examples/bench/table_join_dist_test.cpp`` has every
worker generate its own two tables of ``count`` rows, an int64 key and
an int64 value, keys uniformly random, and join them across the
workers. Here worker ``c`` of ``workers`` draws its rows of each side,
and its rows are block ``c`` of the side's arrays: rows
``[c * rows_per_chip, (c + 1) * rows_per_chip)``.

Keys are uniform on ``[0, key_range)``, ``key_range`` being
``floor(key_dup * workers * rows_per_chip)``, so each side holds about
``1 / key_dup`` rows per key. They come from ``data_seed``, one stream
per worker and side, so every run joins the same keys: the engine's
time depends on where the keys fall. Payloads are uniform on
``[0, payload_range)`` and come from the run's ``seed``, one stream per
worker and side: they are only carried, and change no work. Nothing
here imports the engine.
"""

import numpy as np

SIDES = ("left", "right")


def key_range(rows_per_chip, workers, key_dup):
    """The keys are drawn from ``[0, key_range)``."""
    return int(key_dup * workers * rows_per_chip)


def generate(rows_per_chip, workers, key_dup, payload_range, data_seed,
             seed):
    """``{"left": {"key", "payload"}, "right": {...}}``, each column an
    int64 array of ``workers * rows_per_chip`` rows."""
    n = rows_per_chip
    keys = key_range(n, workers, key_dup)
    out = {}
    for s, side in enumerate(SIDES):
        cols = {"key": np.empty(workers * n, np.int64),
                "payload": np.empty(workers * n, np.int64)}
        for c in range(workers):
            block = slice(c * n, (c + 1) * n)
            rng = np.random.default_rng([data_seed, s, c])
            cols["key"][block] = rng.integers(0, keys, n, dtype=np.int64)
            rng = np.random.default_rng([seed & (2**64 - 1), s, c, 1])
            cols["payload"][block] = rng.integers(0, payload_range, n,
                                                  dtype=np.int64)
        out[side] = cols
    return out
