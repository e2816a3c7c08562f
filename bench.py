"""Headline benchmark: distributed inner-join rows/sec/chip.

Reproduces the reference's flagship experiment (distributed inner join,
``cpp/src/examples/bench/table_join_dist_test.cpp`` driven by
``cpp/src/experiments/run_dist_scaling.py``; published numbers in
``docs/docs/arch.md:148-162``). Baseline comparator: Cylon's 64-rank
MPI result — 1B rows in 4.0 s over 64 ranks = 3.906 M rows/s/rank
(BASELINE.md); ``vs_baseline`` is our single-chip rows/s over that
per-rank rate.

Config: BASELINE.json config 2 — two int64-keyed tables with float64
values, hash inner join, measured steady-state on the real chip.
Steady state means a pipeline of ``CYLON_BENCH_PIPELINE`` (default 12)
back-to-back joins inside one XLA program — distinct key AND value
columns per stage so nothing CSEs — timed over ``CYLON_BENCH_REPS``
dispatches; this amortises per-dispatch host overhead exactly as a
streaming workload would (the reference's 4.0 s / 64-rank number
likewise spans many overlapped exchanges, not one cold call).

The headline is EXCHANGE-INCLUSIVE (VERDICT r4 missing #1): every
pipeline stage hashes fresh keys (``partition_ids``), moves BOTH tables
through the real exchange path (``shuffle_local`` — ragged all-to-all
on TPU, the same code every multi-chip shuffle runs), then joins — the
measured wall covers partition + exchange + join exactly like the
reference's bench wall covers its MPI all-to-all + local join
(``table_join_dist_test.cpp:38-56``). The no-communication local-join
pipeline (the previous headline) is reported alongside as
``local_path_rows_per_sec``.

Emits ONE json line: {"metric", "value", "unit", "vs_baseline", ...}.

``--trace`` additionally runs the headline join ONCE through the
instrumented eager ``dist_join`` path with the flight recorder armed
(``CYLON_TPU_TRACE`` — the pipelined headline hand-rolls its shard_map
and bypasses the recorder by construction), writes the Chrome Trace
Event artifact next to the bench record
(``CYLON_BENCH_TRACE_PATH``, default
``bench_artifacts/bench.trace.json`` — open in
Perfetto / ``chrome://tracing``) and pins its path + event count +
rank-track count + per-stage wall coverage into the JSON record
(:data:`REQUIRED_TRACE_FIELDS`, schema enforced by
``tests/test_bench_guard.py``).

``--join-ab`` races the sort join against the bucketed O(n) hash join
(``ops/hash_join.py``) at ``CYLON_BENCH_JOIN_AB_ROWS`` sizes x
``CYLON_BENCH_JOIN_AB_DISTS`` key distributions, with staged
build/probe walls under ``join.build``/``join.probe`` spans; one
:data:`REQUIRED_JOIN_AB_FIELDS` record per config (the A/B verdict
artifact ``docs/joins.md`` cites).

``--ooc-overlap`` races the pipelined OOC executor
(:mod:`cylon_tpu.pipeline`: bounded prefetch + async checkpointed
spill) against the ``CYLON_TPU_OOC_PREFETCH_DEPTH=0`` sequential
control, per op (``CYLON_BENCH_OOC_OPS``) over a page-cache-evicted
parquet file (see :func:`_bench_ooc_overlap`); one
:data:`REQUIRED_OOC_OVERLAP_FIELDS` record per config with per-stage
idle fractions and a Chrome-trace artifact showing ``ooc.prefetch``
overlapping ``ooc.compute`` (``docs/outofcore.md`` "Pipelined
execution" cites the verdict).
"""

import json
import os
import sys
import time

import numpy as np


def _bench_local_pipeline(n, depth, reps, out_cap, rng):
    """The no-comm pipelined local join (previous headline)."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu import Table
    from cylon_tpu.ops.join import join

    left = Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "a": rng.normal(size=n),
    })
    right = Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "b": rng.normal(size=n),
    })
    # per-stage right tables with INDEPENDENT keys and values: every
    # stage is a full join — nothing (sorts, group ids, gathers) is
    # shareable between stages, so XLA cannot CSE stage work away
    kstack = jnp.asarray(rng.integers(0, n, (depth, n)).astype(np.int64))
    bstack = jnp.asarray(rng.normal(size=(depth, n)))

    @jax.jit
    def step(lt, rt, ks, bs):
        col = rt.column("b").__class__
        total = jnp.int32(0)
        for i in range(depth):
            r = rt.add_column("k", col(ks[i], None, rt.column("k").dtype))
            r = r.add_column("b", col(bs[i], None, rt.column("b").dtype))
            # ordered=False matches the reference's semantics (its sort
            # join emits key order, not left-frame order) and is what
            # the distributed shards run
            res = join(lt, r, on="k", how="inner", out_capacity=out_cap,
                       ordered=False)
            total = total + res.nrows
        return total

    # compile + correctness guard
    nrows_total = int(step(left, right, kstack, bstack))
    assert 0 < nrows_total <= depth * out_cap, f"bad join {nrows_total}"
    single = join(left, right, on="k", how="inner", out_capacity=out_cap)
    assert 0 < int(single.nrows) <= out_cap

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = step(left, right, kstack, bstack)
        float(np.asarray(out))  # host sync
        times.append(time.perf_counter() - t0)
    return depth * n / min(times)


def _bench_exchange_pipeline(n, depth, reps, out_cap, rng):
    """The exchange-inclusive pipelined join: per stage, BOTH sides get
    fresh independent keys/values (nothing CSEs), are hash-partitioned
    (``partition_ids``) and moved through the REAL exchange
    (``shuffle_local`` -> ragged all-to-all on TPU / padded on CPU),
    then joined — all ``depth`` stages inside ONE shard_map-under-jit
    program on a 1-device mesh, like every multi-chip dist_join shard
    runs. W=1 keeps the measurement per-chip (the reference's baseline
    is per-rank) while executing the full collective path."""
    import jax
    import jax.numpy as jnp

    import cylon_tpu as ct
    from cylon_tpu import Table
    from cylon_tpu.column import Column
    from cylon_tpu.ops.hash import partition_ids
    from cylon_tpu.ops.join import join
    from cylon_tpu.parallel import scatter_table
    from cylon_tpu.parallel.shuffle import checked_recv, shuffle_local

    env = ct.CylonEnv(ct.TPUConfig(n_devices=1))
    w = env.world_size
    ax = env.world_axes
    shuf_cap = 2 * n      # uniform keys: 2x expected receive is safe
    join_cap = out_cap

    proto = Table.from_pydict({
        "k": np.zeros(n, np.int64), "v": np.zeros(n)})
    lt0 = scatter_table(env, proto)
    rt0 = scatter_table(env, proto)
    kdt = lt0.column("k").dtype
    vdt = lt0.column("v").dtype

    # per-stage independent keys AND values for BOTH sides: every stage
    # re-hashes, re-exchanges and re-joins fresh data — no stage work is
    # shareable, exactly like the reference's repeated full joins
    kl = jnp.asarray(rng.integers(0, n, (depth, n)).astype(np.int64))
    av = jnp.asarray(rng.normal(size=(depth, n)))
    kr = jnp.asarray(rng.integers(0, n, (depth, n)).astype(np.int64))
    bv = jnp.asarray(rng.normal(size=(depth, n)))

    from jax.sharding import PartitionSpec as P

    def body(lt, rt, kls, avs, krs, bvs):
        total = jnp.int32(0)
        for i in range(depth):
            l = lt.with_nrows(lt.nrows[0])
            l = l.add_column("k", Column(kls[i], None, kdt))
            l = l.add_column("v", Column(avs[i], None, vdt))
            r = rt.with_nrows(rt.nrows[0])
            r = r.add_column("k", Column(krs[i], None, kdt))
            r = r.add_column("v", Column(bvs[i], None, vdt))
            lpid = partition_ids([l.column("k").data], w, [None])
            rpid = partition_ids([r.column("k").data], w, [None])
            lsh, _ = checked_recv(
                shuffle_local(l, lpid, shuf_cap, axis_name=ax), shuf_cap)
            rsh, _ = checked_recv(
                shuffle_local(r, rpid, shuf_cap, axis_name=ax), shuf_cap)
            res = join(lsh, rsh, on="k", how="inner",
                       suffixes=("_l", "_r"), out_capacity=join_cap,
                       ordered=False)
            total = total + res.nrows
        return total.reshape((1,))

    fn = jax.jit(jax.shard_map(
        body, mesh=env.mesh,
        in_specs=(P(ax), P(ax), P(None, ax), P(None, ax), P(None, ax),
                  P(None, ax)),
        out_specs=P(ax)))

    total = int(np.asarray(fn(lt0, rt0, kl, av, kr, bv))[0])
    assert 0 < total <= depth * join_cap, f"bad exchange join {total}"

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(lt0, rt0, kl, av, kr, bv)
        int(np.asarray(out)[0])  # host sync
        times.append(time.perf_counter() - t0)
    # true exchange payload per dispatch, priced exactly like the
    # exchange.bytes_true counter (valid rows x packed u32 word width,
    # both tables, every stage) — the numerator of the roofline fields
    from cylon_tpu import telemetry
    from cylon_tpu.parallel.shuffle import transport_words

    words = transport_words(lt0) + transport_words(rt0)
    bytes_per_dispatch = depth * n * words * 4
    telemetry.counter("exchange.bytes_true",
                      op="bench_exchange").inc(bytes_per_dispatch * reps)
    return depth * n / min(times), bytes_per_dispatch / min(times)


#: headline-record fields the roofline trajectory depends on — main()
#: asserts them before emitting and ``tests/test_bench_guard.py`` pins
#: the set, so a refactor cannot silently drop the bytes/s or
#: fraction-of-peak columns from the BENCH_* history.
REQUIRED_HEADLINE_FIELDS = frozenset({
    "metric", "value", "unit", "vs_baseline",
    "exchange_bytes_per_sec", "fraction_of_hbm_peak", "exchange_note",
})

#: where bench artifacts (Chrome traces and friends) land by default:
#: a dedicated directory, NOT the repo root — committed artifacts stay
#: out of the tree's top level and every record pins the actual path
#: (ISSUE 14 satellite; override per artifact via
#: ``CYLON_BENCH_TRACE_PATH``)
ARTIFACTS_DIR = os.environ.get("CYLON_BENCH_ARTIFACTS_DIR",
                               "bench_artifacts")

#: fields a ``--trace`` run must pin into the headline record — the
#: artifact is only auditable if the record says where it is and how
#: much it holds (``tests/test_bench_guard.py`` pins this set).
REQUIRED_TRACE_FIELDS = frozenset({
    "trace_path", "trace_events", "trace_rank_tracks",
    "trace_stage_coverage", "trace_dropped",
})

#: fields every ``--join-ab`` record must pin (ISSUE 12) — the A/B
#: verdict is only reproducible if each record names the config, both
#: walls, the winner, and whether the bucketed path's overflow
#: fallback fired (``tests/test_bench_guard.py`` pins this set).
REQUIRED_JOIN_AB_FIELDS = frozenset({
    "rows", "distribution", "sort_wall", "hash_wall", "winner",
    "overflow_fallbacks",
})

#: fields every ``--ooc-overlap`` record must pin (ISSUE 13) — the
#: overlap verdict is only auditable if each record names the op, the
#: source model, BOTH walls (overlap on vs the
#: ``CYLON_TPU_OOC_PREFETCH_DEPTH=0`` sequential control), the
#: prefetch hit/miss counters, the hidden-IO seconds, the per-stage
#: idle fractions from the trace, and the trace artifact path
#: (``tests/test_bench_guard.py`` pins this set).
REQUIRED_OOC_OVERLAP_FIELDS = frozenset({
    "op", "rows", "source", "sequential_wall", "overlap_wall",
    "overlap_speedup", "rows_per_sec_sequential",
    "rows_per_sec_overlap", "prefetch_hits", "prefetch_misses",
    "overlap_seconds", "prefetch_compute_overlap_s",
    "idle_fractions_sequential", "idle_fractions_overlap",
    "platform", "trace_path",
})

#: pipeline stages the --ooc-overlap idle-fraction audit reads from
#: the trace (idle fraction = 1 - stage busy seconds / wall)
_OOC_STAGES = ("ooc.prefetch", "ooc.compute", "spill.write_async",
               "spill.write")


def _ooc_stage_stats(evts, wall):
    """Per-stage busy seconds + idle fractions from one run's trace,
    plus the cross-thread seconds where an ``ooc.prefetch`` span
    overlapped an ``ooc.compute`` span — the timeline proof that the
    ingest actually ran DURING compute (0 in the sequential arm by
    construction: both stages share one thread there)."""
    spans, open_spans = [], {}
    for e in evts:
        if e["kind"] == "begin":
            open_spans[e["id"]] = e
        elif e["kind"] == "end":
            b = open_spans.pop(e.get("id"), None)
            if b is not None:
                spans.append((b["name"], b.get("tid"), b["ts"],
                              e["ts"]))
        elif e["kind"] == "complete":
            spans.append((e["name"], e.get("tid"), e["ts"],
                          e["ts"] + e["dur"]))
    busy: dict = {}
    for name, _, t0, t1 in spans:
        busy[name] = busy.get(name, 0.0) + max(t1 - t0, 0.0)
    idle = {s: round(max(1.0 - busy.get(s, 0.0) / wall, 0.0), 4)
            for s in _OOC_STAGES if s in busy}
    pre = [(t0, t1, tid) for n, tid, t0, t1 in spans
           if n == "ooc.prefetch"]
    cmp_ = [(t0, t1, tid) for n, tid, t0, t1 in spans
            if n == "ooc.compute"]
    ov = 0.0
    for p0, p1, ptid in pre:
        for c0, c1, ctid in cmp_:
            if ctid == ptid:
                continue
            lo, hi = max(p0, c0), min(p1, c1)
            if hi > lo:
                ov += hi - lo
    return busy, idle, ov


def _bench_ooc_overlap():
    """ISSUE 13 A/B: pipelined OOC execution (bounded prefetch + async
    checkpointed spill) vs the ``CYLON_TPU_OOC_PREFETCH_DEPTH=0``
    sequential control, per op.

    Source: a real uncompressed-parquet file, page cache evicted
    (``posix_fadvise DONTNEED``) before every pass so reads hit the
    disk. Each record labels its source and platform."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    if os.environ.get("CYLON_TPU_TRACE", "") in ("", "0", "off"):
        os.environ["CYLON_TPU_TRACE"] = "1"

    import jax

    from cylon_tpu import telemetry
    from cylon_tpu.outofcore import ooc_groupby, ooc_sort
    from cylon_tpu.telemetry import trace

    ops = os.environ.get("CYLON_BENCH_OOC_OPS", "sort,groupby").split(",")
    n = int(os.environ.get("CYLON_BENCH_OOC_ROWS", 1_000_000))
    chunk = int(os.environ.get("CYLON_BENCH_OOC_CHUNK", 1 << 16))
    ncols = int(os.environ.get("CYLON_BENCH_OOC_VALUE_COLS", 6))
    reps = int(os.environ.get("CYLON_BENCH_OOC_REPS", 2))
    depth = os.environ.get("CYLON_BENCH_OOC_DEPTH", "2")
    nparts = 8

    tmp = tempfile.mkdtemp(prefix="cylon_ooc_overlap_")
    rng = np.random.default_rng(7)
    cols = {"k": rng.integers(0, n, n).astype(np.int64),
            "g": rng.integers(0, 64, n).astype(np.int64)}
    for i in range(ncols):
        cols[f"v{i}"] = rng.normal(size=n)
    path = os.path.join(tmp, "src.parquet")
    pq.write_table(pa.table(cols), path, compression="none")
    del cols

    def _evict():
        # cold-ish reads both arms: evict the source from page cache so
        # every pass reads the device, like an SF100 source would
        fd = os.open(path, os.O_RDONLY)
        try:
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        except (AttributeError, OSError):
            pass
        finally:
            os.close(fd)

    def _chunks():
        _evict()
        pf = pq.ParquetFile(path)
        for b in pf.iter_batches(batch_size=chunk):
            yield {c: b.column(c).to_numpy(zero_copy_only=False)
                   for c in b.schema.names}

    def _run(op, depth_env, seq):
        os.environ["CYLON_TPU_OOC_PREFETCH_DEPTH"] = depth_env
        rdir = os.path.join(tmp, f"ck_{op}_{depth_env}_{seq}")
        odir = os.path.join(tmp, f"out_{op}_{depth_env}_{seq}")
        os.makedirs(odir)
        nsink = [0]

        def sink(pdf):
            # durable output: the sorted table is PERSISTED (what an
            # at-scale OOC sort is for) — rides the async writer
            p = os.path.join(odir, f"part{nsink[0]:05d}.npz")
            nsink[0] += 1
            with open(p, "wb") as f:
                np.savez(f, **{c: pdf[c].to_numpy()
                               for c in pdf.columns})
                f.flush()
                os.fsync(f.fileno())

        trace.clear()
        c0 = {k: telemetry.total(k) for k in
              ("ooc.prefetch_hits", "ooc.prefetch_misses",
               "ooc.overlap_seconds")}
        t0 = time.perf_counter()
        if op == "sort":
            ooc_sort(_chunks, ["k"],
                     n_partitions=nparts, chunk_rows=chunk,
                     resume_dir=rdir, sink=sink)
        elif op == "groupby":
            # Q1-shaped pre-combine: sum+min+max per value column plus
            # a count — the chunked streaming-aggregation workload
            # (tpch q1_ooc) whose per-chunk device compute the
            # prefetcher hides chunk pulls behind
            aggs = [("v0", "count", "cnt")]
            for i in range(ncols):
                aggs += [(f"v{i}", "sum", f"s{i}"),
                         (f"v{i}", "min", f"mn{i}"),
                         (f"v{i}", "max", f"mx{i}")]
            ooc_groupby(_chunks, ["g"], aggs,
                        chunk_rows=chunk, resume_dir=rdir)
        else:
            raise ValueError(f"unknown --ooc-overlap op {op!r}")
        wall = time.perf_counter() - t0
        evts = trace.events()
        deltas = {k: telemetry.total(k) - v for k, v in c0.items()}
        shutil.rmtree(rdir, ignore_errors=True)
        shutil.rmtree(odir, ignore_errors=True)
        return wall, evts, deltas

    records = []
    try:
        for op in ops:
            arms = {}
            for label, d in (("sequential", "0"),
                             ("overlap", depth)):
                best = None
                for rep in range(max(reps, 1)):
                    wall, evts, deltas = _run(op, d,
                                              f"{label}{rep}")
                    if best is None or wall < best[0]:
                        best = (wall, evts, deltas)
                arms[label] = best
            seq_wall, seq_evts, _ = arms["sequential"]
            ov_wall, ov_evts, ov_deltas = arms["overlap"]
            _, seq_idle, _ = _ooc_stage_stats(seq_evts, seq_wall)
            _, ov_idle, xov = _ooc_stage_stats(ov_evts, ov_wall)
            tpath = os.path.abspath(
                os.path.join(ARTIFACTS_DIR,
                f"ooc_overlap.{op}.disk.trace.json"))
            telemetry.write_chrome_trace(
                tpath, telemetry.to_chrome_trace(
                    [{"rank": 0, "clock_offset": 0.0,
                      "events": ov_evts}]))
            record = {
                "metric": "ooc_overlap_ab",
                "op": op,
                "rows": n,
                "source": "disk",
                "value_cols": ncols,
                "chunk_rows": chunk,
                "n_partitions": nparts,
                "prefetch_depth": int(depth),
                "sequential_wall": round(seq_wall, 4),
                "overlap_wall": round(ov_wall, 4),
                "overlap_speedup": round(seq_wall / ov_wall, 4),
                "rows_per_sec_sequential": round(n / seq_wall, 1),
                "rows_per_sec_overlap": round(n / ov_wall, 1),
                "prefetch_hits": int(
                    ov_deltas["ooc.prefetch_hits"]),
                "prefetch_misses": int(
                    ov_deltas["ooc.prefetch_misses"]),
                "overlap_seconds": round(
                    float(ov_deltas["ooc.overlap_seconds"]), 4),
                "prefetch_compute_overlap_s": round(xov, 4),
                "idle_fractions_sequential": seq_idle,
                "idle_fractions_overlap": ov_idle,
                "reps": reps,
                "platform": jax.default_backend(),
                "trace_path": tpath,
            }
            missing = REQUIRED_OOC_OVERLAP_FIELDS - record.keys()
            assert not missing, \
                f"ooc-overlap record dropped {missing}"
            _emit_record(record)
            records.append(record)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return records


def _join_ab_keys(n, dist, rng):
    """Left-side key distribution per config; the right side is always
    ~unique (uniform over [0, n)) so the OUTPUT stays ~n rows while the
    left side's duplication drives the bucket-chain pressure (bucket
    load depends on key multiplicity, not value skew — the murmur hash
    randomises values)."""
    if dist == "uniform":
        lk = rng.integers(0, n, n)          # ~Poisson(1) duplication
    elif dist == "zipf":
        # heavy-head key frequencies: a few keys carry huge chains
        # (straddling the bucket width), the tail is near-unique
        lk = np.minimum(rng.zipf(1.5, n), n) - 1
    elif dist == "dup64":
        # every key ~64x duplicated: every chain exceeds the default
        # width-16 budget, so the bucketed hash path MUST take its
        # overflow fallback — this config measures that path's cost
        lk = rng.integers(0, max(n // 64, 1), n)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    rk = rng.integers(0, n, n)
    return lk.astype(np.int64), rk.astype(np.int64)


def _bench_join_ab(rows_list, dists, reps):
    """A/B race: the sort join vs the shipped ``algorithm="hash"``
    bucketed build/probe, per size x key distribution, plus staged
    build/probe walls (``join.build`` / ``join.probe`` spans — the
    same series ``RequestProfiler`` attributes serve-request stages
    from). Emits one :data:`REQUIRED_JOIN_AB_FIELDS` record per config
    and returns the list."""
    import time as _time

    import jax

    from cylon_tpu import Table, telemetry
    from cylon_tpu.ops import hash_join
    from cylon_tpu.ops.join import join
    from cylon_tpu.utils import tracing

    # the race is sort vs the BUCKETED kernel: pin the impl so the
    # record is reproducible from its own command line regardless of
    # the shipped DEFAULT_HASH_IMPL verdict (override to taste)
    os.environ.setdefault("CYLON_TPU_JOIN_HASH_IMPL", "bucketed")

    rng = np.random.default_rng(11)
    records = []
    for n in rows_list:
        n_reps = max(1, reps if n < 50_000_000 else 1)
        for dist in dists:
            lk, rk = _join_ab_keys(n, dist, rng)
            lt = Table.from_pydict({"k": lk, "a": rng.normal(size=n)})
            rt = Table.from_pydict({"k": rk, "b": rng.normal(size=n)})
            out_cap = 4 * n
            walls, out_rows = {}, {}
            ovf0 = telemetry.counter("join.overflow_fallbacks").value
            for alg in ("sort", "hash"):
                times = []
                for rep in range(n_reps + 1):  # rep 0 = compile
                    t0 = _time.perf_counter()
                    res = join(lt, rt, on="k", how="inner",
                               algorithm=alg, out_capacity=out_cap,
                               ordered=False)
                    nr = int(res.nrows)  # full program sync
                    if rep:
                        times.append(_time.perf_counter() - t0)
                assert 0 < nr <= out_cap, f"bad A/B join {nr}"
                walls[alg] = min(times)
                out_rows[alg] = nr
            overflowed = (telemetry.counter(
                "join.overflow_fallbacks").value - ovf0)
            assert out_rows["sort"] == out_rows["hash"], \
                f"A/B row-set mismatch {out_rows}"
            # staged walls: build and probe as separate dispatches
            # under the join.build/join.probe spans (stage attribution)
            bj = jax.jit(lambda kd, nr_: hash_join.build_phase(
                [kd], [None], nr_))
            pj = jax.jit(lambda kd, nr_, tbl, bw: hash_join.probe_phase(
                [kd], [None], nr_, tbl, bw))
            kd_b, kd_p = lt.column("k").data, rt.column("k").data
            table = bwords = None
            build_s = probe_s = None
            for rep in range(2):  # rep 0 = compile
                t0 = _time.perf_counter()
                with tracing.span("join.build"):
                    table, ovf, _, bwords = jax.block_until_ready(
                        bj(kd_b, lt.nrows))
                if rep:
                    build_s = _time.perf_counter() - t0
                t0 = _time.perf_counter()
                with tracing.span("join.probe"):
                    mask, _ = jax.block_until_ready(
                        pj(kd_p, rt.nrows, table, bwords))
                if rep:
                    probe_s = _time.perf_counter() - t0
            record = {
                "metric": "join_ab",
                "rows": n,
                "distribution": dist,
                "sort_wall": round(walls["sort"], 4),
                "hash_wall": round(walls["hash"], 4),
                "winner": ("hash" if walls["hash"] < walls["sort"]
                           else "sort"),
                "overflow_fallbacks": int(overflowed),
                "out_rows": out_rows["sort"],
                "build_s": round(build_s, 4),
                "probe_s": round(probe_s, 4),
                "build_overflow_rows": int(ovf),
                "reps": n_reps,
                "hash_impl": hash_join.hash_impl(),
                "bucket_width": hash_join.bucket_width(),
                "platform": jax.default_backend(),
            }
            missing = REQUIRED_JOIN_AB_FIELDS - record.keys()
            assert not missing, f"join-ab record dropped {missing}"
            _emit_record(record)
            records.append(record)
            del lt, rt
    return records


def _traced_headline_join(n: int, rng) -> dict:
    """One eager ``dist_join`` over every visible device with the
    flight recorder armed; writes the Chrome-trace artifact and returns
    the :data:`REQUIRED_TRACE_FIELDS` block for the headline record.

    Runs the INSTRUMENTED ``parallel.dist_ops`` path (stage spans,
    exchange instants with true/padded bytes, per-shard row counter
    tracks), unlike the pipelined headline which fuses its own
    shard_map. ``CYLON_TPU_FORCE_DIST`` keeps the exchange path live on
    a W=1 mesh (the real-chip default), so the artifact always carries
    exchange slices."""
    # force-arm: an inherited CYLON_TPU_TRACE=0/off must not make the
    # explicit --trace flag silently record nothing
    if os.environ.get("CYLON_TPU_TRACE", "") in ("", "0", "off"):
        os.environ["CYLON_TPU_TRACE"] = "1"

    import cylon_tpu as ct
    from cylon_tpu import Table, telemetry
    from cylon_tpu.parallel import dist_join, scatter_table
    from cylon_tpu.telemetry import trace

    env = ct.CylonEnv(ct.TPUConfig())
    lt = scatter_table(env, Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "a": rng.normal(size=n)}))
    rt = scatter_table(env, Table.from_pydict({
        "k": rng.integers(0, n, n).astype(np.int64),
        "b": rng.normal(size=n)}))
    trace.clear()
    # FORCE_DIST only for THIS join (restored after): the exchange path
    # must run even on a W=1 real chip, but later suite legs must keep
    # their configured world==1 short-circuit semantics
    prev_force = os.environ.get("CYLON_TPU_FORCE_DIST")
    os.environ["CYLON_TPU_FORCE_DIST"] = "1"
    try:
        dist_join(env, lt, rt, on="k", how="inner")
    finally:
        if prev_force is None:
            os.environ.pop("CYLON_TPU_FORCE_DIST", None)
        else:
            os.environ["CYLON_TPU_FORCE_DIST"] = prev_force
    evts = trace.events()
    coverage = trace.stage_coverage(evts, "dist_join")
    path = os.environ.get("CYLON_BENCH_TRACE_PATH",
                          os.path.join(ARTIFACTS_DIR,
                                       "bench.trace.json"))
    doc = telemetry.to_chrome_trace(trace.rank_buffers(env),
                                    world=env.world_size)
    telemetry.write_chrome_trace(path, doc)
    pids = {e.get("pid") for e in doc["traceEvents"]}
    return {
        "trace_path": os.path.abspath(path),
        "trace_events": len(evts),
        "trace_rank_tracks": len(pids),
        "trace_stage_coverage": (round(coverage, 4)
                                 if coverage is not None else None),
        # silent-loss audit: events the ring bound evicted before the
        # export — a non-zero value means the artifact is a WINDOW,
        # not the whole run (raise CYLON_TPU_TRACE_EVENTS)
        "trace_dropped": trace.dropped(),
    }


def _emit_record(line: dict):
    """Single stdout sink for the headline JSON record: attaches the
    telemetry ``metrics`` block (byte / overflow / retry context from
    ``cylon_tpu.telemetry.bench_metrics``) so the BENCH_* trajectory
    carries more than wall time — schema pinned by
    ``tests/test_bench_guard.py``. Telemetry must never fail a bench."""
    line = dict(line)
    try:
        from cylon_tpu import telemetry

        line["metrics"] = telemetry.bench_metrics()
    except Exception as e:  # pragma: no cover - import-time breakage
        line["metrics"] = {"telemetry_error": f"{type(e).__name__}: {e}"}
    print(json.dumps(line))


def main():
    if "--ooc-overlap" in sys.argv[1:]:
        _bench_ooc_overlap()
        return
    if "--join-ab" in sys.argv[1:]:
        rows_list = [int(x) for x in os.environ.get(
            "CYLON_BENCH_JOIN_AB_ROWS",
            "1000000,10000000,100000000").split(",")]
        dists = os.environ.get("CYLON_BENCH_JOIN_AB_DISTS",
                               "uniform,zipf,dup64").split(",")
        reps = int(os.environ.get("CYLON_BENCH_JOIN_AB_REPS", 3))
        _bench_join_ab(rows_list, dists, reps)
        return
    do_trace = "--trace" in sys.argv[1:] or os.environ.get(
        "CYLON_BENCH_TRACE", "") not in ("", "0", "off")
    n = int(os.environ.get("CYLON_BENCH_ROWS", 1_000_000))
    reps = int(os.environ.get("CYLON_BENCH_REPS", 5))
    depth = int(os.environ.get("CYLON_BENCH_PIPELINE", 12))
    # E[output rows] == n for uniform keys; 2x headroom stays safe while
    # keeping the capacity-bounded buffers (and their gathers) tight
    out_cap = 2 * n

    rng = np.random.default_rng(7)
    xchg_rows_per_sec, xchg_bytes_per_sec = _bench_exchange_pipeline(
        n, depth, reps, out_cap, rng)
    local_rows_per_sec = _bench_local_pipeline(n, depth, reps, out_cap,
                                               rng)

    import jax

    from cylon_tpu import telemetry

    baseline_per_rank = 1e9 / 4.0 / 64  # Cylon 64-rank MPI (BASELINE.md)
    record = {
        "metric": "dist_inner_join_exchange_rows_per_sec_per_chip",
        "value": round(xchg_rows_per_sec, 1),
        "unit": "rows/s/chip",
        "vs_baseline": round(xchg_rows_per_sec / baseline_per_rank, 3),
        "local_path_rows_per_sec": round(local_rows_per_sec, 1),
        "local_path_vs_baseline": round(
            local_rows_per_sec / baseline_per_rank, 3),
        # roofline position (VERDICT r5): true exchange payload per
        # wall second against the v5e HBM peak. The headline runs on a
        # W=1 mesh where the all-to-all is a SELF-DMA through HBM —
        # there is no ICI traffic to price, hence the HBM denominator
        # and the explicit label
        "exchange_bytes_per_sec": round(xchg_bytes_per_sec, 1),
        "fraction_of_hbm_peak": round(telemetry.fraction_of_peak(
            xchg_bytes_per_sec, jax.devices()[0].device_kind), 6),
        "exchange_note": ("W=1 mesh: the all-to-all is a self-DMA, so "
                          "bytes/s is against the HBM roofline "
                          "(819 GB/s/chip), not ICI"),
    }
    if do_trace:
        record.update(_traced_headline_join(n, rng))
        missing_t = REQUIRED_TRACE_FIELDS - record.keys()
        assert not missing_t, f"trace record dropped fields {missing_t}"
    missing = REQUIRED_HEADLINE_FIELDS - record.keys()
    assert not missing, f"headline record dropped fields {missing}"
    _emit_record(record)


if __name__ == "__main__":
    main()
