"""Pallas kernel parity: interpret-mode kernels vs the jnp fallbacks.

The reference's hot-loop kernels are unit-tested against golden results
(``cpp/test/partition_test.cpp``, ``groupby_test``); here the oracle is
the pure-XLA implementation the kernels replace — they must be
bit-identical (hash) / numerically equal (segment sum).
"""

import numpy as np
import jax.numpy as jnp
import jax.ops
import pytest

from cylon_tpu.ops import hash as rowhash
from cylon_tpu.ops import pallas_kernels as pk


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("CYLON_PALLAS", "1")


def test_row_hash_matches_jnp_chain(rng, pallas_interpret, monkeypatch):
    a = jnp.asarray(rng.integers(-(2**40), 2**40, 1000), jnp.int64)
    b = jnp.asarray(rng.normal(size=1000))
    v = jnp.asarray(rng.integers(0, 2, 1000), bool)

    got = rowhash.hash_columns([a, b], [v, None])
    monkeypatch.setenv("CYLON_PALLAS", "0")
    want = rowhash.hash_columns([a, b], [v, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_partition_ids_fused_mod(rng, pallas_interpret, monkeypatch):
    a = jnp.asarray(rng.integers(0, 10**6, 777), jnp.int64)
    got = rowhash.partition_ids([a], 8)
    monkeypatch.setenv("CYLON_PALLAS", "0")
    want = rowhash.partition_ids([a], 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).min() >= 0 and np.asarray(got).max() < 8


def test_row_hash_unaligned_length(rng, pallas_interpret):
    # capacity not a multiple of the 1024-lane block
    a = jnp.asarray(rng.integers(0, 100, 130), jnp.int32)
    h = rowhash.hash_columns([a])
    assert h.shape == (130,) and h.dtype == jnp.uint32



def test_groupby_sum_via_pallas(rng, pallas_interpret):
    from cylon_tpu import Table
    from cylon_tpu.ops.groupby import groupby_aggregate
    import pandas as pd

    k = rng.integers(0, 50, 400)
    x = rng.normal(size=400).astype(np.float32)
    t = Table.from_pydict({"k": k, "x": x})
    out = groupby_aggregate(t, ["k"], [("x", "sum")])
    pdres = pd.DataFrame({"k": k, "x": x}).groupby("k")["x"].sum()
    got = out.to_pandas().set_index("k")["x_sum"]
    np.testing.assert_allclose(got.loc[pdres.index].to_numpy(),
                               pdres.to_numpy(), rtol=1e-4)



def test_row_hash_multiblock(rng, pallas_interpret, monkeypatch):
    # cap > one 8x1024 tile: exercises the multi-block grid indexing
    a = jnp.asarray(rng.integers(-(2**40), 2**40, 20_000), jnp.int64)
    got = rowhash.hash_columns([a])
    monkeypatch.setenv("CYLON_PALLAS", "0")
    want = rowhash.hash_columns([a])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))



@pytest.mark.parametrize("kind,np_ref", [
    ("add", np.cumsum),
    ("max", np.maximum.accumulate),
])
def test_scan32_parity(rng, pallas_interpret, kind, np_ref):
    for n in (3, 8192, 16384 + 7, 60_001):
        for dt in (np.int32, np.uint32, np.float32):
            lo = 0 if dt == np.uint32 else -100
            x = rng.integers(lo, 100, n).astype(dt) if dt != np.float32 \
                else rng.normal(size=n).astype(np.float32)
            got = np.asarray(pk.scan32(jnp.asarray(x), kind))
            if dt == np.float32 and kind == "add":
                # tile-wise association differs from sequential order;
                # compare against the exact (f64) prefix sums with a
                # reassociation-sized tolerance
                want64 = np.cumsum(x.astype(np.float64))
                tol = np.abs(x).sum() * 1e-6 + 1e-4
                np.testing.assert_allclose(got, want64, atol=tol)
            else:
                np.testing.assert_array_equal(got, np_ref(x).astype(dt))


def test_fast_cumsum_cummax_fallback(rng):
    """Off-TPU (no interpret), fast_* must be the plain XLA ops."""
    from cylon_tpu.ops import kernels

    x = jnp.asarray(rng.integers(-50, 50, 999), jnp.int32)
    np.testing.assert_array_equal(np.asarray(kernels.fast_cumsum(x)),
                                  np.cumsum(np.asarray(x)))
    np.testing.assert_array_equal(np.asarray(kernels.fast_cummax(x)),
                                  np.maximum.accumulate(np.asarray(x)))


def test_join_parity_with_scan_kernel(rng, pallas_interpret):
    """The join's expansion scans ride scan32 under interpret mode —
    results must match the pure-XLA path exactly."""
    import pandas as pd

    from cylon_tpu import Table
    from cylon_tpu.ops.join import join

    n = 500
    lp = pd.DataFrame({"k": rng.integers(0, 40, n), "a": rng.normal(size=n)})
    rp = pd.DataFrame({"k": rng.integers(0, 40, n), "b": rng.normal(size=n)})
    got = join(Table.from_pandas(lp), Table.from_pandas(rp), on="k",
               how="inner", out_capacity=16 * n).to_pandas()
    want = lp.merge(rp, on="k")
    cols = ["k", "a", "b"]
    pd.testing.assert_frame_equal(
        got[cols].sort_values(cols).reset_index(drop=True),
        want[cols].sort_values(cols).reset_index(drop=True),
        check_dtype=False)


def test_pair_max_scan_matches_u64_cummax(rng, pallas_interpret):
    """The lex-max pair scan must be bit-identical to cummax of
    (hi << 32) | lo — the ordering forward_fill's u64 encoding relies
    on — including ties in hi and zeros."""
    for n in (9, 8192, 30_000):
        hi = rng.integers(0, 50, n).astype(np.uint32)  # many hi ties
        lo = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
        hi[rng.random(n) < 0.3] = 0
        gh, gl = pk.pair_max_scan(jnp.asarray(hi), jnp.asarray(lo))
        enc = (hi.astype(np.uint64) << 32) | lo.astype(np.uint64)
        want = np.maximum.accumulate(enc)
        got = (np.asarray(gh).astype(np.uint64) << 32) \
            | np.asarray(gl).astype(np.uint64)
        np.testing.assert_array_equal(got, want)


def test_dist_join_under_interpret_mode(env8, rng, pallas_interpret):
    """Distributed join on the mesh with CYLON_PALLAS=1 (interpreted
    on the CPU): inside shard_map the operands are device-varying, where the interpret
    evaluator cannot run the scan kernels — the gates must fall back to
    the XLA forms cleanly (regression: the pair-scan's cross-row
    combine once used a lax.scan whose unvarying carry failed the vma
    type check at trace time)."""
    import pandas as pd

    from cylon_tpu import Table
    from cylon_tpu.parallel import dist_join, dist_num_rows

    n = 400
    lp = pd.DataFrame({"k": rng.integers(0, 30, n), "a": rng.normal(size=n)})
    rp = pd.DataFrame({"k": rng.integers(0, 30, n), "b": rng.normal(size=n)})
    j = dist_join(env8, Table.from_pandas(lp), Table.from_pandas(rp),
                  on="k", how="inner")
    assert dist_num_rows(j) == len(lp.merge(rp, on="k"))
