"""Host IO: CSV / Parquet / JSON ingest and egress.

Parity: ``cpp/src/cylon/io/`` (csv_read_config 152 LoC, csv_write_config,
parquet_config, arrow_io) and the multi-file threaded readers of
``table.cpp:788-795`` (CSV) / ``:1121-1127`` (Parquet). Arrow does the
parsing here exactly as in the reference; the TPU-specific part is the
hand-off — columns are dictionary-encoded and padded into device tables,
and a distributed read slices row blocks across the mesh
(``slice=True``, parity with pycylon's per-rank file assignment).
"""

from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

from cylon_tpu import resilience
from cylon_tpu.config import CSVReadOptions, CSVWriteOptions
from cylon_tpu.errors import IOError_
from cylon_tpu.table import Table


def _native_ok() -> bool:
    try:
        from cylon_tpu import native

        return native.available()
    except Exception:
        return False


def _column_types_arrow(column_types):
    """{name: "int64"|"float64"|"str"|np.dtype-like} -> pyarrow types."""
    import numpy as np
    import pyarrow as pa

    out = {}
    for name, t in (column_types or {}).items():
        if t in ("str", "string", str):
            out[name] = pa.string()
        else:
            out[name] = pa.from_numpy_dtype(np.dtype(t))
    return out or None


def _arrow_csv_opts(options: CSVReadOptions):
    """(ReadOptions, ParseOptions, ConvertOptions) for pyarrow.csv."""
    import pyarrow.csv as pacsv

    read_opts = pacsv.ReadOptions(
        use_threads=options.use_threads,
        block_size=options.block_size,
        skip_rows=options.skip_rows,
        column_names=(list(options.column_names)
                      if options.column_names else None),
        autogenerate_column_names=options.auto_generate_column_names,
    )
    parse_opts = pacsv.ParseOptions(
        delimiter=options.delimiter,
        ignore_empty_lines=options.ignore_emptylines,
        quote_char=(options.quote_char if options.use_quoting else False),
        double_quote=options.double_quote,
        escape_char=(options.escaping_character if options.use_escaping
                     else False),
        newlines_in_values=options.has_newlines_in_values,
    )
    convert_kw = dict(
        include_columns=(list(options.use_cols) if options.use_cols
                         else None),
        include_missing_columns=options.include_missing_columns,
        strings_can_be_null=options.strings_can_be_null,
        column_types=_column_types_arrow(options.column_types),
    )
    # pyarrow treats empty lists as "nothing is null/true/false"; only
    # override its defaults when the caller actually set spellings
    if options.na_values is not None:
        convert_kw["null_values"] = list(options.na_values)
    if options.true_values is not None:
        convert_kw["true_values"] = list(options.true_values)
    if options.false_values is not None:
        convert_kw["false_values"] = list(options.false_values)
    convert = pacsv.ConvertOptions(**convert_kw)
    return read_opts, parse_opts, convert


def _arrow_csv_read(path, options: CSVReadOptions):
    """One CSV parse, under the retry engine: transient failures
    (``resilience.is_retryable`` — remote-FS connection resets, the
    ``io_read`` injection point) re-attempt with backoff; parse errors
    and missing files raise immediately (deterministic, not worth
    retrying). Every sharded/chunked/threaded reader funnels through
    here, so the whole CSV surface inherits the policy."""
    import pyarrow.csv as pacsv

    read_opts, parse_opts, convert = _arrow_csv_opts(options)

    def _read():
        resilience.inject("io_read", str(path))
        return pacsv.read_csv(path, read_options=read_opts,
                              parse_options=parse_opts,
                              convert_options=convert)

    return resilience.retrying(_read, label=f"read_csv {path}")


def read_csv(paths, options: CSVReadOptions | None = None,
             env=None, capacity: int | None = None,
             engine: str = "auto"):
    """Read one or many CSVs (parity: ``FromCSV``, table.cpp:788 — many
    paths read concurrently on threads). With ``env``, rows are sliced
    over the mesh (returns a distributed DataFrame).

    ``engine``: ``"native"`` uses the C++ chunk-parallel loader
    (``cylon_tpu.native``), ``"arrow"`` pyarrow, ``"auto"`` native when
    built and the options allow it (plain delimiter/header reads)."""
    from cylon_tpu.frame import DataFrame

    options = options or CSVReadOptions()
    single = isinstance(paths, (str, bytes))
    path_list = [paths] if single else list(paths)

    # the native engine covers plain reads plus quoting/na_values/dtype
    # overrides; the rest (skip_rows, explicit/auto column names,
    # escaping, embedded newlines, bool spellings, arrow's implicit
    # default null spellings for strings, missing-column filling,
    # non-{int64,float64,str} dtype overrides) routes to arrow
    from cylon_tpu.native import csv_dtype_ok as _native_dtype_ok

    plain = (options.skip_rows == 0 and options.column_names is None
             and not options.auto_generate_column_names
             and not options.use_escaping
             and not options.has_newlines_in_values
             and options.true_values is None
             and options.false_values is None
             and options.double_quote
             and not options.include_missing_columns
             and not (options.strings_can_be_null
                      and options.na_values is None)
             and all(_native_dtype_ok(t)
                     for t in (options.column_types or {}).values()))
    if engine == "native" or (engine == "auto" and plain and _native_ok()):
        if not plain:
            from cylon_tpu.errors import NotImplemented_

            raise NotImplemented_(
                "native csv engine does not support skip_rows/"
                "column_names/escaping/newlines-in-values/bool "
                "spellings/missing-column filling/default null "
                "spellings/non-{int64,float64,str} dtype overrides; "
                "use engine='arrow'")
        from cylon_tpu import native

        kw = dict(
            quote_char=(options.quote_char if options.use_quoting
                        else None),
            na_values=(list(options.na_values)
                       if options.na_values else None),
            column_types=options.column_types,
            strings_can_be_null=options.strings_can_be_null,
        )
        try:
            if len(path_list) == 1:
                t = native.csv_to_table(path_list[0], options.delimiter,
                                        capacity=capacity, **kw)
            else:
                workers = (min(8, len(path_list))
                           if options.concurrent_file_reads else 1)
                with ThreadPoolExecutor(max_workers=workers) as ex:
                    tables = list(ex.map(
                        lambda p: native.csv_to_table(
                            p, options.delimiter, **kw),
                        path_list))
                from cylon_tpu.ops.selection import concat_tables

                t = concat_tables(tables, capacity=capacity)
        except Exception as e:
            raise IOError_(f"csv read failed: {e}") from e
        if options.use_cols:
            t = t.select(list(options.use_cols))
        df = DataFrame._wrap(t)
        if env is not None or options.slice:
            from cylon_tpu.context import CylonEnv
            from cylon_tpu.parallel import scatter_table

            df = DataFrame._wrap(scatter_table(env or CylonEnv(), t))
        return df
    try:
        if len(path_list) == 1:
            atables = [_arrow_csv_read(path_list[0], options)]
        else:
            workers = (min(8, len(path_list))
                       if options.concurrent_file_reads else 1)
            with ThreadPoolExecutor(max_workers=workers) as ex:
                atables = list(ex.map(
                    lambda p: _arrow_csv_read(p, options), path_list))
    except Exception as e:  # pyarrow raises its own hierarchy
        raise IOError_(f"csv read failed: {e}") from e
    import pyarrow as pa

    at = pa.concat_tables(atables) if len(atables) > 1 else atables[0]
    t = Table.from_arrow(at, capacity)
    df = DataFrame._wrap(t)
    if env is not None or options.slice:
        from cylon_tpu.context import CylonEnv
        from cylon_tpu.parallel import scatter_table

        df = DataFrame._wrap(scatter_table(env or CylonEnv(), t))
    return df


def _exchange_meta(local_meta: dict) -> list[dict]:
    """All-gather small host-side metadata (row counts, dtypes,
    dictionary values) across processes. Single-process: identity.
    Multi-controller: pickled bytes ride a padded uint8
    ``process_allgather`` — the moral equivalent of the reference's
    MPI_Allgather of UCX worker addresses at bootstrap
    (``net/ucx/ucx_communicator.cpp:67-97``): tiny host metadata over
    DCN, never table data."""
    import jax

    if jax.process_count() == 1:
        return [local_meta]
    import pickle

    import numpy as np
    from jax.experimental import multihost_utils

    blob = np.frombuffer(pickle.dumps(local_meta), np.uint8)
    n = np.asarray([blob.size], np.int64)
    sizes = np.asarray(multihost_utils.process_allgather(n)).reshape(-1)
    pad = int(sizes.max())
    padded = np.zeros(pad, np.uint8)
    padded[: blob.size] = blob
    all_blobs = np.asarray(multihost_utils.process_allgather(padded))
    return [pickle.loads(all_blobs[p, : int(sizes[p])].tobytes())
            for p in range(all_blobs.shape[0])]


def read_csv_sharded(paths: Sequence[str], env,
                     options: CSVReadOptions | None = None,
                     local_capacity: int | None = None):
    """Scale-out ingest: ONE FILE PER MESH WORKER. Shard ``s`` parses
    ``paths[s]`` (a thread per file) and places its rows directly on its
    own device — at no point does any host build a concatenated global
    buffer (contrast ``read_csv(env=...)``, which parses centrally then
    scatters). Under ``jax.distributed`` each process parses only the
    files of its addressable shards, so ingest memory AND parse time
    scale out with hosts.

    Parity: the reference's per-rank reads — each rank its own file,
    a std::thread per file (``table.cpp:788-795``) — which is what lets
    Cylon load SF100+ datasets no single node could hold. Dictionary
    (string) columns are unified across shards via a host-metadata
    exchange (values only, never rows); per-shard codes are remapped on
    their own devices (one tiny gather each).

    Returns a mesh-distributed DataFrame.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cylon_tpu.column import Column, Dictionary
    from cylon_tpu.errors import InvalidArgument
    from cylon_tpu.frame import DataFrame

    options = options or CSVReadOptions()
    paths = list(paths)
    w = env.world_size
    if len(paths) != w:
        raise InvalidArgument(
            f"read_csv_sharded needs exactly one path per worker "
            f"({w}), got {len(paths)}")
    devs = list(env.mesh.devices.flat)
    pid = jax.process_index()
    mine = [s for s in range(w) if devs[s].process_index == pid]

    with ThreadPoolExecutor(max_workers=min(8, max(len(mine), 1))) as ex:
        ats = dict(zip(mine, ex.map(
            lambda s: _arrow_csv_read(paths[s], options), mine)))
    if options.use_cols:
        ats = {s: at.select(list(options.use_cols)) for s, at in ats.items()}

    # per-shard parse + pad on the shard's own device
    counts_local = {s: ats[s].num_rows for s in mine}
    tables = {}
    for s in mine:
        t = Table.from_arrow(ats[s], None)
        tables[s] = t
    del ats

    # host-metadata exchange: counts, schema agreement, dictionaries
    local_names = [list(tables[s].column_names) for s in mine]
    for s, ns in zip(mine[1:], local_names[1:]):
        if ns != local_names[0]:
            raise InvalidArgument(
                f"shard files disagree on columns: {paths[mine[0]]} has "
                f"{local_names[0]}, {paths[s]} has {ns}")
    meta = {
        "counts": counts_local,
        "names": local_names[0],
        "schema": {},
    }
    some = tables[mine[0]]
    for name, c in some.columns.items():
        meta["schema"][name] = {
            "dtype": str(np.dtype(c.data.dtype)),
            "has_validity": any(tables[s].column(name).validity is not None
                                for s in mine),
            "dict_values": sorted(
                {v for s in mine
                 for v in (tables[s].column(name).dictionary.values
                           if tables[s].column(name).dictionary is not None
                           else ())}),
            "is_dict": some.column(name).dtype.is_dictionary,
        }
    all_meta = _exchange_meta(meta)

    counts = np.zeros(w, np.int64)
    for m in all_meta:
        for s, n in m["counts"].items():
            counts[s] = n
    names = list(some.column_names)
    for m in all_meta:
        # column names AND order must agree across processes, or each
        # process would build a structurally different program (silent
        # SPMD divergence)
        if m["names"] != names:
            raise InvalidArgument(
                f"shard files disagree on columns across processes: "
                f"{names} vs {m['names']}")
    schema = {}
    for name in names:
        ms = [m["schema"][name] for m in all_meta]
        dts = {m["dtype"] for m in ms}
        if len(dts) > 1:
            raise InvalidArgument(
                f"column {name!r} parsed with different dtypes across "
                f"shard files: {sorted(dts)}; pass explicit dtypes")
        schema[name] = {
            "dtype": ms[0]["dtype"],
            "has_validity": any(m["has_validity"] for m in ms),
            "is_dict": ms[0]["is_dict"],
            "dict_values": sorted({v for m in ms for v in m["dict_values"]}),
        }

    from cylon_tpu.utils import pow2_bucket

    if local_capacity is not None and local_capacity < counts.max():
        raise InvalidArgument(
            f"local_capacity {local_capacity} is below the largest shard "
            f"file's row count {int(counts.max())}")
    cap_l = local_capacity or pow2_bucket(int(counts.max()))
    gshape_rows = w * cap_l
    row_sh = env.row_sharding

    def assemble(per_shard):  # {s: [cap_l]-array} -> global sharded array
        arrs = [jax.device_put(per_shard[s], devs[s]) for s in mine]
        shape = (gshape_rows,) + arrs[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, row_sh, arrs)

    cols = {}
    for name in names:
        sch = schema[name]
        shared = (Dictionary(np.asarray(sch["dict_values"], object))
                  if sch["is_dict"] else None)
        data_shards, valid_shards = {}, {}
        for s in mine:
            c = tables[s].column(name)
            data = np.asarray(c.data)[: counts[s]]
            if sch["is_dict"]:
                old = (c.dictionary.values if c.dictionary is not None
                       else np.asarray([], object))
                if len(old):
                    lut = np.searchsorted(sch["dict_values"], old
                                          ).astype(np.int32)
                    data = lut[np.clip(data, 0, len(old) - 1)]
                else:
                    data = np.zeros_like(data)
            pad = np.zeros(cap_l - counts[s], data.dtype)
            data_shards[s] = np.concatenate([data, pad])
            if sch["has_validity"]:
                v = (np.asarray(c.validity)[: counts[s]]
                     if c.validity is not None
                     else np.ones(counts[s], bool))
                valid_shards[s] = np.concatenate(
                    [v, np.zeros(cap_l - counts[s], bool)])
        gdata = assemble(data_shards)
        gval = assemble(valid_shards) if sch["has_validity"] else None
        proto = tables[mine[0]].column(name)
        cols[name] = Column(gdata, gval, proto.dtype, shared)

    nrows = jax.make_array_from_single_device_arrays(
        (w,), row_sh,
        [jax.device_put(np.asarray([counts[s]], np.int32), devs[s])
         for s in mine])
    return DataFrame._wrap(Table(cols, nrows))


def read_csv_chunks(path, chunk_rows: int,
                    options: CSVReadOptions | None = None):
    """Out-of-core CSV source: yield fixed-capacity ``Table`` chunks
    without ever materialising the file on the host.

    The reference's streaming op-graph exists to process inputs larger
    than memory as chunks arrive (``ops/dis_join_op.cpp:21-72`` fed by
    arrow record batches; incremental receive reassembly in
    ``arrow_all_to_all.cpp:173-214``). This is the ingest end of that
    pipeline: pyarrow's incremental CSV reader parses one block at a
    time, rows are re-packed into chunks of EXACTLY ``chunk_rows``
    capacity (every chunk shape-identical, so the downstream per-chunk
    shuffle/pre-combine programs compile once and are reused), and host
    memory stays O(block + chunk) regardless of file size.

    Feed the chunks to :class:`cylon_tpu.ops_graph.DisJoinOp` /
    ``GroupByOp`` etc. — with ``env=`` they hash-shuffle over the mesh
    as they arrive, so no single host ever holds the dataset.

    String columns dictionary-encode per chunk; downstream concat /
    join unify dictionaries (``ops/dictenc.py``), and mesh shuffles
    hash dictionary VALUES, so per-chunk code spaces are safe.
    """
    import pyarrow.csv as pacsv

    # validate and open EAGERLY (this is not a generator function):
    # bad arguments or a missing file raise at the call site, not at
    # some distant first next() inside a streaming loop
    if chunk_rows <= 0:
        raise IOError_(f"chunk_rows must be positive, got {chunk_rows}")
    options = options or CSVReadOptions()
    read_opts, parse_opts, convert = _arrow_csv_opts(options)

    def _open():
        resilience.inject("io_read", str(path))
        return pacsv.open_csv(path, read_options=read_opts,
                              parse_options=parse_opts,
                              convert_options=convert)

    try:
        reader = resilience.retrying(_open,
                                     label=f"read_csv_chunks {path}")
    except Exception as e:
        raise IOError_(f"csv chunk read failed: {e}") from e
    return _csv_chunk_iter(reader, chunk_rows)


def _csv_chunk_iter(reader, chunk_rows: int):
    import pyarrow as pa

    pending: list = []   # record batches, together < chunk_rows + block
    npend = 0
    try:
        with reader:
            for batch in reader:
                if batch.num_rows == 0:
                    continue
                pending.append(batch)
                npend += batch.num_rows
                while npend >= chunk_rows:
                    tbl = pa.Table.from_batches(pending)
                    yield Table.from_arrow(tbl.slice(0, chunk_rows),
                                           capacity=chunk_rows)
                    rest = tbl.slice(chunk_rows)
                    pending = rest.to_batches() if rest.num_rows else []
                    npend = rest.num_rows
    except Exception as e:
        raise IOError_(f"csv chunk read failed: {e}") from e
    if npend:
        yield Table.from_arrow(pa.Table.from_batches(pending),
                               capacity=chunk_rows)


def read_parquet_chunks(path, chunk_rows: int,
                        columns: Sequence[str] | None = None):
    """Out-of-core Parquet source: ``chunk_rows``-capacity chunks via
    pyarrow's row-group/batch iterator — the Parquet twin of
    :func:`read_csv_chunks` (parity surface: ``FromParquet``,
    table.cpp:1121, streamed instead of materialised)."""
    import pyarrow.parquet as pq

    if chunk_rows <= 0:
        raise IOError_(f"chunk_rows must be positive, got {chunk_rows}")

    def _open():
        resilience.inject("io_read", str(path))
        return pq.ParquetFile(path)  # eager: missing file raises here

    try:
        pf = resilience.retrying(_open,
                                 label=f"read_parquet_chunks {path}")
    except Exception as e:
        raise IOError_(f"parquet chunk read failed: {e}") from e
    return _parquet_chunk_iter(pf, chunk_rows, columns)


def _parquet_chunk_iter(pf, chunk_rows: int, columns):
    import pyarrow as pa

    try:
        for batch in pf.iter_batches(batch_size=chunk_rows,
                                     columns=columns):
            if batch.num_rows:
                yield Table.from_arrow(pa.Table.from_batches([batch]),
                                       capacity=chunk_rows)
    except Exception as e:
        raise IOError_(f"parquet chunk read failed: {e}") from e


def write_csv(df, path, options: CSVWriteOptions | None = None):
    """Parity: ``WriteCSV`` (table.cpp:243)."""
    options = options or CSVWriteOptions()
    pdf = df.to_pandas() if hasattr(df, "to_pandas") else df
    pdf.to_csv(path, sep=options.delimiter, index=False,
               header=options.include_header)


def write_csv_sharded(df, paths: Sequence[str], env,
                      options: CSVWriteOptions | None = None) -> list:
    """Scale-out egress: ONE FILE PER MESH WORKER — shard ``s``'s rows
    go to ``paths[s]``, no host ever assembles the whole table.

    The write-side mirror of :func:`read_csv_sharded` and the parity of
    the reference's per-rank ``WriteCSV`` (every rank writes its own
    output file, ``cpp/test/test_utils.hpp`` golden files are per-rank
    for exactly this reason). Under ``jax.distributed`` each process
    writes only the shards it can address, so egress memory and IO
    scale out with hosts. Returns the paths this process wrote.
    """
    import jax

    from cylon_tpu.errors import InvalidArgument
    from cylon_tpu.parallel import dtable
    from cylon_tpu.table import Table

    options = options or CSVWriteOptions()
    t: Table = df.table if hasattr(df, "table") else df
    t = dtable.scatter_table(env, t)
    w = env.world_size
    paths = list(paths)
    if len(paths) != w:
        raise InvalidArgument(
            f"write_csv_sharded needs exactly one path per worker "
            f"({w}), got {len(paths)}")
    # one fetch serves both the poison check and the per-shard counts
    # (dist_num_rows would fetch a second time; message kept identical)
    counts = dtable.host_counts(t)
    cap_l = dtable.local_capacity(t)
    if (counts > cap_l).any():
        from cylon_tpu.errors import OutOfCapacity

        raise OutOfCapacity(
            f"shard row counts {counts.tolist()} exceed local capacity "
            f"{cap_l}; re-run with a larger out_capacity / skew factor")
    devs = list(env.mesh.devices.flat)
    pid = jax.process_index()
    mine = [s for s in range(w) if devs[s].process_index == pid]

    def shard_buf(arr, dev):
        # this device's block only — never the global buffer
        return next(s for s in arr.addressable_shards
                    if s.device == dev).data

    # ONE batched transfer for every shard block this process writes
    # (per-buffer fetches pay a host sync each — the
    # Table._host_columns convention)
    fetches = {}
    for s in mine:
        for name, c in t.columns.items():
            fetches[(s, name, "d")] = shard_buf(c.data, devs[s])
            if c.validity is not None:
                fetches[(s, name, "v")] = shard_buf(c.validity, devs[s])
    fetched = dict(zip(fetches, jax.device_get(list(fetches.values()))))

    import pandas as pd

    written = []
    for s in mine:
        cols = {}
        for name, c in t.columns.items():
            data = fetched[(s, name, "d")][:counts[s]]
            validity = (fetched[(s, name, "v")][:counts[s]]
                        if c.validity is not None else None)
            cols[name] = c.decode_host(data, validity)
        pd.DataFrame(cols).to_csv(paths[s], sep=options.delimiter,
                                  index=False,
                                  header=options.include_header)
        written.append(paths[s])
    return written


def read_parquet(paths, env=None, capacity: int | None = None,
                 columns: Sequence[str] | None = None,
                 options: "ParquetOptions | None" = None,
                 string_storage="dict"):
    """Parity: ``FromParquet`` (table.cpp:1121, behind CYLON_PARQUET —
    here always available via pyarrow). ``options`` is the
    :class:`cylon_tpu.config.ParquetOptions` builder mirror
    (``io/parquet_config.hpp``)."""
    import pyarrow.parquet as pq

    from cylon_tpu.config import ParquetOptions
    from cylon_tpu.frame import DataFrame

    options = options or ParquetOptions()
    if columns is None:
        columns = options.use_cols
    single = isinstance(paths, (str, bytes))
    path_list = [paths] if single else list(paths)

    def _read_one(p):
        def _r():
            resilience.inject("io_read", str(p))
            return pq.read_table(p, columns=columns)

        return resilience.retrying(_r, label=f"read_parquet {p}")

    try:
        if len(path_list) == 1 or not options.concurrent_file_reads:
            atables = [_read_one(p) for p in path_list]
        else:
            with ThreadPoolExecutor(max_workers=min(8, len(path_list))) as ex:
                atables = list(ex.map(_read_one, path_list))
    except Exception as e:
        raise IOError_(f"parquet read failed: {e}") from e
    import pyarrow as pa

    at = pa.concat_tables(atables) if len(atables) > 1 else atables[0]
    t = Table.from_arrow(at, capacity, string_storage)
    df = DataFrame._wrap(t)
    if env is not None:
        from cylon_tpu.parallel import scatter_table

        df = DataFrame._wrap(scatter_table(env, t))
    return df


def write_parquet(df, path, options: "ParquetOptions | None" = None):
    """Parity: ``WriteParquet`` (table.cpp:1148) with the
    ``ParquetOptions`` writer properties (compression, row-group size,
    dictionary encoding, column subset)."""
    import pyarrow.parquet as pq

    from cylon_tpu.config import ParquetOptions

    options = options or ParquetOptions()
    at = df.to_arrow() if hasattr(df, "to_arrow") else df
    if options.write_cols is not None:
        at = at.select(list(options.write_cols))
    comp = options.compression
    pq.write_table(
        at, path,
        compression=None if comp in ("none", None) else comp,
        row_group_size=options.row_group_size,
        use_dictionary=options.use_dictionary)


def read_json(path, env=None, capacity: int | None = None):
    """JSON-lines ingest (parity: pycylon json read helpers)."""
    import pyarrow.json as pajson

    from cylon_tpu.frame import DataFrame

    try:
        at = pajson.read_json(path)
    except Exception as e:
        raise IOError_(f"json read failed: {e}") from e
    t = Table.from_arrow(at, capacity)
    df = DataFrame._wrap(t)
    if env is not None:
        from cylon_tpu.parallel import scatter_table

        df = DataFrame._wrap(scatter_table(env, t))
    return df
