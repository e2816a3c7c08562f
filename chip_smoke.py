#!/usr/bin/env python3
"""Prove that the engine's main path runs on a real TPU.

``python chip_smoke.py`` (one chip) drives what a user drives, through
the public entry points, and checks every answer against a plain
pandas reference:

* TPC-H SF1 (6M-row lineitem): ``dbgen.generate`` -> ``tpch.ingest`` ->
  Q1/Q3/Q5/Q6 through the whole-query compiled path
  (``tpch.compiled``), compared with ``tpch.reference``.
* The exchange-inclusive join that ``bench.py`` times: hash partition
  -> ``shuffle_local`` (the ragged all-to-all) -> local join inside one
  shard_map, at 10M x 10M int64-keyed rows with f64 payloads, compared
  with ``pandas.merge`` by row count and payload checksums. Then the
  distributed ``shuffle``/``dist_join`` forced through the exchange at
  world 1 (string payloads included), and the Pallas bucket-join
  kernels against their jnp twins.

``python chip_smoke.py --chips 4`` runs only the multi-chip path: the
``dist_join -> dist_groupby -> dist_sort`` chain at 10M rows per chip
and TPC-H SF4 Q3/Q5 distributed over the four chips, each after
printing each device's shard bytes (``--only chain|tpch`` runs one).

Floats are held to rtol 1e-9 (the tier-1 TPC-H tolerance) and the
observed max relative error is printed. Every phase runs even when an
earlier one failed; any failure exits 1. Without a TPU the script exits
1 before doing any work. The last line of a passing run is the JSON
device record.
"""

import argparse
import json
import sys
import time
import traceback

import numpy as np

#: float tolerance: the one the tier-1 TPC-H tests hold
RTOL = 1e-9


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(name: str, diff, **fields) -> None:
    """Print one result line; raise on a mismatch."""
    status = "ok" if diff is None else f"MISMATCH {diff}"
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    _log(f"{name}: {status} {extra}")
    if diff is not None:
        raise AssertionError(f"{name}: {diff}")


def _host(out):
    """A query result as the client sees it."""
    if hasattr(out, "to_pandas"):
        return out.to_pandas().reset_index(drop=True)
    return float(np.asarray(out))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _shard_bytes(label: str, arrays) -> dict:
    """Print and return the bytes each device holds of ``arrays``."""
    per = {}
    for a in arrays:
        for s in a.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    _log(f"shard bytes {label}: "
         + " ".join(f"dev{d}={b}" for d, b in sorted(per.items())))
    return per


def _table_arrays(table):
    return [c.data for c in table.columns.values()]


# ------------------------------------------------------------ TPC-H

def _tpch_data(sf: float, seed: int):
    from cylon_tpu import tpch
    from cylon_tpu.tpch import dbgen

    data, gen_s = _timed(lambda: dbgen.generate(sf, seed))
    frames, ingest_s = _timed(lambda: tpch.ingest(data))
    pdfs = dbgen.to_pandas(data)
    _log(f"tpch sf={sf} seed={seed}: lineitem_rows="
         f"{len(data['lineitem']['l_orderkey'])} generate_s={gen_s:.3f} "
         f"ingest_s={ingest_s:.3f}")
    return frames, pdfs


def tpch_phase(sf: float, seed: int) -> None:
    """Q1/Q3/Q5/Q6 whole-query compiled on one device vs pandas."""
    from cylon_tpu import tpch
    from cylon_tpu.testing import compare_results
    from cylon_tpu.tpch import reference

    frames, pdfs = _tpch_data(sf, seed)
    failed = []
    for q in ("q1", "q3", "q5", "q6"):
        run = tpch.compiled(q)
        got, first_s = _timed(lambda: _host(run(frames)))
        got, warm_s = _timed(lambda: _host(run(frames)))
        want = getattr(reference, f"{q}_pandas")(pdfs)
        diff, err = compare_results(got, want, rtol=RTOL)
        rows = len(got) if hasattr(got, "columns") else 1
        try:
            _check(f"tpch {q}", diff, compile_s=f"{first_s - warm_s:.3f}",
                   first_call_s=f"{first_s:.3f}", warm_s=f"{warm_s:.3f}",
                   rows=rows, max_rel_err=f"{err:.3e}")
        except AssertionError as e:
            failed.append(str(e))
    if failed:
        raise AssertionError("; ".join(failed))


# --------------------------------------------------------- exchange

def exchange_join_program(env, n: int):
    """The exchange-inclusive join of ``bench.py`` as one program:
    per shard, hash-partition both sides, move them through
    ``shuffle_local`` (ragged all-to-all on TPU), join, and reduce the
    result to (rows, key sum, left/right payload sums)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.ops.hash import partition_ids
    from cylon_tpu.ops.join import join
    from cylon_tpu.parallel.shuffle import (checked_recv, poison,
                                            shuffle_local)

    w, ax = env.world_size, env.world_axes
    shuf_cap = 2 * n          # uniform keys: 2x the expected receive
    join_cap = 2 * n          # uniform keys in [0, n): ~n matches

    def body(lt, rt):
        lt = lt.with_nrows(lt.nrows[0])
        rt = rt.with_nrows(rt.nrows[0])
        lpid = partition_ids([lt.column("k").data], w, [None])
        rpid = partition_ids([rt.column("k").data], w, [None])
        lsh, lbad = checked_recv(
            shuffle_local(lt, lpid, shuf_cap, axis_name=ax), shuf_cap)
        rsh, rbad = checked_recv(
            shuffle_local(rt, rpid, shuf_cap, axis_name=ax), shuf_cap)
        res = join(lsh, rsh, on="k", how="inner", suffixes=("_l", "_r"),
                   out_capacity=join_cap, ordered=False)
        res = poison(res, lbad, rbad)
        valid = jnp.arange(res.capacity) < res.nrows

        def total(c, zero):
            return jnp.where(valid, res.column(c).data, zero).sum()

        return (res.nrows.reshape(1), total("k", 0).reshape(1),
                total("v_l", 0.0).reshape(1), total("v_r", 0.0).reshape(1))

    return jax.jit(jax.shard_map(body, mesh=env.mesh,
                                 in_specs=(P(ax), P(ax)),
                                 out_specs=(P(ax),) * 4)), join_cap


def exchange_phase(env, n: int, seed: int) -> None:
    """The bench's exchange-inclusive join at ``n`` x ``n`` rows vs
    ``pandas.merge``: row count, key sum and payload sums."""
    import pandas as pd

    from cylon_tpu import Table
    from cylon_tpu.parallel import scatter_table
    from cylon_tpu.testing import compare_results

    rng = np.random.default_rng(seed)
    left = pd.DataFrame({"k": rng.integers(0, n, n, dtype=np.int64),
                         "v": rng.random(n)})
    right = pd.DataFrame({"k": rng.integers(0, n, n, dtype=np.int64),
                          "v": rng.random(n)})
    lt = scatter_table(env, Table.from_pandas(left))
    rt = scatter_table(env, Table.from_pandas(right))
    fn, join_cap = exchange_join_program(env, n)

    def run():
        return [np.asarray(x) for x in fn(lt, rt)]

    _, first_s = _timed(run)
    (rows, ksum, lsum, rsum), warm_s = _timed(run)
    m = left.merge(right, on="k", suffixes=("_l", "_r"))
    got = pd.DataFrame({"rows": [int(rows.sum())], "k_sum": [ksum.sum()],
                        "v_l_sum": [lsum.sum()], "v_r_sum": [rsum.sum()]})
    want = pd.DataFrame({"rows": [len(m)], "k_sum": [m.k.sum()],
                         "v_l_sum": [m.v_l.sum()], "v_r_sum": [m.v_r.sum()]})
    if (rows > join_cap).any():
        diff, err = f"join overflowed its capacity {join_cap}", 0.0
    else:
        diff, err = compare_results(got, want, rtol=RTOL)
    _check(f"exchange join {n}x{n}", diff, rows=int(rows.sum()),
           compile_s=f"{first_s - warm_s:.3f}", first_call_s=f"{first_s:.3f}",
           warm_s=f"{warm_s:.3f}", max_rel_err=f"{err:.3e}")


def forced_exchange_phase(env, seed: int) -> None:
    """``shuffle`` and ``dist_join`` through the exchange at world 1
    (``CYLON_TPU_FORCE_DIST``; the dist ops otherwise short-circuit),
    with a device-bytes string column riding the ragged all-to-all."""
    import os

    import pandas as pd

    from cylon_tpu.parallel import dist_join, dtable, shuffle
    from cylon_tpu.table import Table
    from cylon_tpu.testing import compare_results

    rng = np.random.default_rng(seed)
    n = 20_000
    keys = rng.integers(0, n, n).astype(np.int64)
    vals = rng.normal(size=n)
    com = np.array([f"row {i} of the ragged exchange" for i in range(n)],
                   object)
    t = Table.from_pydict({"k": keys, "v": vals, "s": com},
                          string_storage="bytes")
    rk = rng.integers(0, n, n // 2).astype(np.int64)
    rv = rng.normal(size=n // 2)
    prev = os.environ.get("CYLON_TPU_FORCE_DIST")
    os.environ["CYLON_TPU_FORCE_DIST"] = "1"
    try:
        got = dtable.dist_to_pandas(env, shuffle(env, t, ["k"]))
        j = dtable.dist_to_pandas(env, dist_join(
            env, t.select(["k", "v"]),
            Table.from_pydict({"k": rk, "w": rv}), on="k"))
    finally:
        if prev is None:
            os.environ.pop("CYLON_TPU_FORCE_DIST")
        else:
            os.environ["CYLON_TPU_FORCE_DIST"] = prev
    # f64 payloads cross the TPU exchange as an f32 (hi, lo) pair: held
    # to RTOL like every float, with the observed error printed
    want = pd.DataFrame({"k": keys, "v": vals, "s": com})
    diff, err = compare_results(got, want, rtol=RTOL, ignore_order=True)
    _check("forced shuffle (strings)", diff, rows=len(got),
           max_rel_err=f"{err:.3e}")
    ej = pd.DataFrame({"k": keys, "v": vals}).merge(
        pd.DataFrame({"k": rk, "w": rv}), on="k")
    diff, err = compare_results(j[list(ej.columns)], ej, rtol=RTOL,
                                ignore_order=True)
    _check("forced dist_join", diff, rows=len(j), max_rel_err=f"{err:.3e}")


def bucket_kernel_phase(seed: int) -> None:
    """The Pallas bucket build/probe kernels, compiled by Mosaic,
    against their bit-identical jnp twins."""
    import jax.numpy as jnp

    from cylon_tpu.ops import hash_join as hj
    from cylon_tpu.ops import pallas_kernels as pk

    assert not pk._interpret(), "Pallas would run in interpret mode"
    rng = np.random.default_rng(seed)
    n, nb, width = 1 << 16, 1 << 14, 4
    bkeys = jnp.asarray(rng.integers(0, nb, n // 4), jnp.uint32)
    pkeys = jnp.asarray(rng.integers(0, nb, n), jnp.uint32)
    bbids = (bkeys % nb).astype(jnp.int32)
    pbids = (pkeys % nb).astype(jnp.int32)
    t1, o1 = pk.bucket_build(bbids, nb, width)
    t2, o2 = hj._build_jnp(bbids, nb, width)
    diff = None
    if not (np.array_equal(np.asarray(t1), np.asarray(t2))
            and int(o1) == int(o2)):
        diff = "bucket_build differs from its jnp twin"
    else:
        m1 = pk.bucket_probe(pbids, [pkeys], t1, [bkeys])
        m2 = hj._probe_jnp(pbids, [pkeys], t1, [bkeys])
        if not np.array_equal(np.asarray(m1), np.asarray(m2)):
            diff = "bucket_probe differs from its jnp twin"
    _check("pallas bucket kernels", diff, rows=n, buckets=nb)


# -------------------------------------------------------- multichip

def multichip_tpch_phase(env, sf: float, seed: int) -> None:
    """TPC-H Q3/Q5 distributed over the mesh against pandas, with the
    lineitem shard bytes per device."""
    from cylon_tpu import tpch
    from cylon_tpu.parallel import scatter_table
    from cylon_tpu.testing import compare_results
    from cylon_tpu.tpch import reference

    w = env.world_size
    frames, pdfs = _tpch_data(sf, seed)
    per = _shard_bytes("lineitem", _table_arrays(
        scatter_table(env, frames["lineitem"].table)))
    failed = _spread_failures(per, w)
    for q in ("q3", "q5"):
        got, wall_s = _timed(lambda: _host(getattr(tpch, q)(frames,
                                                            env=env)))
        want = getattr(reference, f"{q}_pandas")(pdfs)
        diff, err = compare_results(got, want, rtol=RTOL)
        try:
            _check(f"tpch {q} distributed x{w}", diff, wall_s=f"{wall_s:.3f}",
                   rows=len(got), max_rel_err=f"{err:.3e}")
        except AssertionError as e:
            failed.append(str(e))
    if failed:
        raise AssertionError("; ".join(failed))


def multichip_chain_phase(env, rows_per_chip: int, seed: int) -> None:
    """dist_join -> dist_groupby -> dist_sort against pandas, with the
    shard bytes per device of its input and its result. The pandas
    reference is computed on a host thread while the chip works."""
    from concurrent.futures import ThreadPoolExecutor

    import pandas as pd

    from cylon_tpu import Table
    from cylon_tpu.parallel import (dist_groupby, dist_join, dist_sort,
                                    dtable, scatter_table)
    from cylon_tpu.testing import compare_results

    w = env.world_size
    rng = np.random.default_rng(seed)
    n = rows_per_chip * w
    left = pd.DataFrame({"k": rng.integers(0, n, n, dtype=np.int64),
                         "a": rng.random(n)})
    right = pd.DataFrame({"k": rng.integers(0, n, n, dtype=np.int64),
                          "b": rng.random(n)})

    def reference():
        return (left.merge(right, on="k").groupby("k", as_index=False)
                .agg(a=("a", "sum"), b=("b", "mean")))

    with ThreadPoolExecutor(1) as pool:
        want = pool.submit(reference)
        lt = scatter_table(env, Table.from_pandas(left))
        rt = scatter_table(env, Table.from_pandas(right))
        failed = _spread_failures(
            _shard_bytes("chain left", _table_arrays(lt)), w)

        def chain():
            j = dist_join(env, lt, rt, on="k", how="inner")
            g = dist_groupby(env, j, ["k"], [("a", "sum"), ("b", "mean")])
            s = dist_sort(env, g, ["k"])
            failed.extend(_spread_failures(
                _shard_bytes("chain result", _table_arrays(s)), w))
            return dtable.dist_to_pandas(env, s)

        got, wall_s = _timed(chain)
        want = want.result()
    got = got.rename(columns=dict(zip(got.columns, want.columns)))
    diff, err = compare_results(got, want, rtol=RTOL)
    try:
        _check(f"dist join->groupby->sort x{w}", diff, rows=len(got),
               wall_s=f"{wall_s:.3f}", max_rel_err=f"{err:.3e}")
    except AssertionError as e:
        failed.append(str(e))
    if failed:
        raise AssertionError("; ".join(failed))


def _spread_failures(per: dict, w: int) -> list:
    """A failure unless every one of the ``w`` devices holds data."""
    if len(per) != w or min(per.values()) == 0:
        return [f"data not on all {w} devices: {per}"]
    return []


# ------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phases")
    ap.add_argument("--only", choices=("chain", "tpch"),
                    help="with --chips 4: run one multi-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    import cylon_tpu as ct

    env = ct.CylonEnv(ct.TPUConfig(n_devices=args.chips))
    dev = devices[0]
    _log(f"device_kind={dev.device_kind} count={len(devices)} "
         f"mesh={env.world_size} jax={jax.__version__}")
    for d in env.mesh.devices.flat:
        _log(f"device {d.id}: bytes_limit="
             f"{(d.memory_stats() or {}).get('bytes_limit')}")

    if args.chips == 4:
        phases = [
            ("multichip_chain", lambda: multichip_chain_phase(
                env, 10_000_000, args.seed)),
            ("multichip_tpch", lambda: multichip_tpch_phase(
                env, 4.0, args.seed)),
        ]
        phases = [p for p in phases
                  if args.only is None or p[0].endswith(args.only)]
    else:
        phases = [
            ("tpch", lambda: tpch_phase(1.0, args.seed)),
            ("exchange", lambda: exchange_phase(env, 10_000_000, args.seed)),
            ("forced_exchange", lambda: forced_exchange_phase(env, args.seed)),
            ("bucket_kernels", lambda: bucket_kernel_phase(args.seed)),
        ]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # reported, and the run exits 1 below
            # XLA's out-of-memory reports list every allocation: keep
            # the head (the verdict and the largest ones) and the tail
            tb = traceback.format_exc()
            print(tb if len(tb) < 8000 else
                  f"{tb[:5000]}\n[...]\n{tb[-2500:]}", file=sys.stderr)
            failed.append(name)
        _log(f"phase {name}: {'FAILED' if name in failed else 'passed'} "
             f"in {time.perf_counter() - t0:.3f}s")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": env.world_size}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
