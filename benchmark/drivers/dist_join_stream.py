"""Driver of Cylon's distributed inner join: one client joins the same
two distributed tables back to back, closed loop, as Cylon's
``table_join_dist_test.cpp`` times its join.

Each worker (one chip) holds its own partition of both tables
(``djoin_gen``). The keys are the configuration's (``data_seed``), the
same for every run, as dbgen's rows are for a TPC-H scale factor: the
engine's time depends on where the keys fall (on a v5e 2x2 two runs
over one set of keys agree to 0.3%, key sets drawn from different
seeds took 4.53-4.68 s a join). The payloads are the run's seed's; they
are only carried and change no work. A pass is one
``left.merge(right, on=..., how=..., env=env)`` on the two distributed
``DataFrame``s: the engine's ``dist_join`` with its defaulted
capacities (count probe, hash partition, all-to-all exchange, local
sort join on every chip). It ends when the output is ready on every
chip and its row counts are on the host. Each pass's output replaces
the previous one's.

Configuration keys: ``workers`` (chips), ``rows_per_chip`` (per side),
``key_dup``, ``payload_range``, ``data_seed`` (see ``djoin_gen``).
Traffic keys: ``on``, ``how``, ``warm_passes``.
"""

import sys
import time
import traceback

import numpy as np

#: the configuration of a CPU rehearsal
TINY = {"rows_per_chip": 2048}


def _key_range(config):
    import djoin_gen

    return djoin_gen.key_range(config["rows_per_chip"], config["workers"],
                               config["key_dup"])


def _data(config, seed):
    import djoin_gen

    return djoin_gen.generate(config["rows_per_chip"], config["workers"],
                              config["key_dup"], config["payload_range"],
                              config["data_seed"], seed)


def setup(config, traffic, seed, devices):
    """Draw both tables, lay each worker's partition on its chip and
    run ``warm_passes`` joins (the first compiles)."""
    import cylon_tpu as ct
    from cylon_tpu.frame import DataFrame

    if len(devices) != config["workers"]:
        raise ValueError(f"{config['workers']} workers, "
                         f"{len(devices)} chips")
    data = _data(config, seed)
    env = ct.CylonEnv(ct.TPUConfig(devices=list(devices)))
    state = {"data": data, "env": env, "traffic": traffic,
             "key_range": _key_range(config),
             "frames": {side: DataFrame(cols, env=env)
                        for side, cols in data.items()}}
    for _ in range(traffic["warm_passes"]):
        one_pass(state)
    return state


def one_pass(state):
    """One join; the output's total row count, or the exception."""
    import jax

    state.pop("out", None)
    left, right = state["frames"]["left"], state["frames"]["right"]
    with jax.profiler.TraceAnnotation("bench.join"):
        try:
            out = left.merge(right, on=state["traffic"]["on"],
                             how=state["traffic"]["how"], env=state["env"])
            table = jax.block_until_ready(out.to_table())
            rows = int(np.asarray(table.nrows).sum())
        except Exception as e:  # counted as a failed pass
            traceback.print_exc()
            return e
    state["out"] = out
    return rows


def window(state, seconds):
    """Whole passes until ``seconds`` have passed. Each pass's seconds
    and the host CPU seconds it took go to standard error."""
    rows, unit_s = [], []
    t0 = t = time.perf_counter()
    while t - t0 < seconds:
        cpu = time.process_time()
        rows.append(one_pass(state))
        unit_s.append(time.perf_counter() - t)
        t += unit_s[-1]
        print(f"pass {len(unit_s)}: {unit_s[-1]:.4f} s, {rows[-1]!r} rows; "
              f"host CPU {time.process_time() - cpu:.4f} s",
              file=sys.stderr, flush=True)
    return {"units": len(unit_s), "elapsed": t - t0, "unit_s": unit_s,
            "rows": rows}


def end_to_end(state, win):
    return {"stream_s": win["elapsed"] / win["units"]}


def _row_bytes(cols):
    return sum(a.itemsize for a in cols.values())


def least_bytes(state):
    """HBM bytes one join must move at least, over all chips: both
    sides read once and the output's rows (the key and both payloads)
    written once, at the columns' own widths."""
    data = state["data"]
    left, right = data["left"], data["right"]
    n = state["key_range"]
    rows = int(np.dot(np.bincount(left["key"], minlength=n),
                      np.bincount(right["key"], minlength=n)))
    read = sum(len(c["key"]) * _row_bytes(c) for c in data.values())
    out = rows * (_row_bytes(left) + right["payload"].itemsize)
    return read + out


def least_ici_bytes(state):
    """Bytes one chip must send to other chips per join at least: of
    its rows of both sides, the share ``(W - 1) / W`` that uniform
    hashing sends elsewhere, at the columns' own widths."""
    w = state["env"].world_size
    total = 0
    for cols in state["data"].values():
        n = len(next(iter(cols.values()))) // w
        total += n * _row_bytes(cols)
    return total * (w - 1) / w


def _host_rows(out):
    """The output's valid rows on the host: ``{name: (data, validity
    or None)}``, shard after shard."""
    table = out.to_table()
    counts = np.asarray(table.nrows)
    cap = table.capacity // len(counts)
    cols = {}
    for name, col in table.columns.items():
        cols[name] = tuple(
            None if a is None else np.concatenate([
                block[:min(c, cap)] for block, c in
                zip(np.asarray(a).reshape(len(counts), cap), counts)])
            for a in (col.data, col.validity))
    return cols


def _compare(want, rows, got, stray=0):
    """``(attempted, failed, checks)``: each pass's output row count
    (``rows``) against the reference's, and the per-key numbers ``got``
    of the last pass (None: it has no output) with its ``stray`` rows
    against ``want``."""
    import djoin_ref

    total = int(want[0].sum())
    wrong = [r != total for r in rows]
    keys = (int(np.count_nonzero(want[0])) if got is None
            else djoin_ref.wrong_keys(want, got, stray))
    failed = sum(wrong) + (keys > 0 and not wrong[-1])
    return len(rows), failed, {"wrong_passes": (sum(wrong), 0),
                               "wrong_keys": (keys, 0)}


def check(state, win):
    """Read the last pass's output to the host and free the device
    state, then hold every pass's row count, and the last output key
    by key, to the reference."""
    import djoin_ref

    out = state.pop("out", None)
    got, stray = None, 0
    if out is not None:
        cols = _host_rows(out)
        del out
        (k, kv), (x, xv), (y, yv) = (cols[c] for c in
                                     ("key", "payload_x", "payload_y"))
        got, stray = djoin_ref.observed(k, x, y, state["key_range"])
        # a null in the output is a wrong row: no input holds one
        stray += sum(int(np.count_nonzero(~v)) for v in (kv, xv, yv)
                     if v is not None)
    state.pop("frames", None)
    state.pop("env", None)
    data = state["data"]
    want = djoin_ref.expected(data["left"], data["right"],
                              state["key_range"])
    return _compare(want, win["rows"], got, stray)


def control(config, traffic, seed):
    """The check of :func:`check` with the reference join in the
    program's place, its columns cast to int32, the integer type below
    the configuration's int64: it has to fail."""
    import djoin_ref

    data = _data(config, seed)
    n = _key_range(config)
    want = djoin_ref.expected(data["left"], data["right"], n)
    rows = djoin_ref.join(data["left"], data["right"], np.int32)
    got, stray = djoin_ref.observed(*rows, n)
    return _compare(want, [len(rows[0])], got, stray)


# ---------------------------------------------------------------- faults
# Each breaks the engine's timed path; a rehearsal with it has to come
# out not correct.

def _altered():
    """One output payload off by one, where ``dist_join`` returns it."""
    from cylon_tpu import frame
    from cylon_tpu.column import Column

    dist_join = frame.dist_join

    def wrong_join(*a, **kw):
        out = dist_join(*a, **kw)
        name = next(n for n in out.columns if n.startswith("payload"))
        c = out.column(name)
        return out.add_column(name, Column(c.data.at[0].add(1), c.validity,
                                           c.dtype, c.dictionary))

    frame.dist_join = wrong_join


def _paired():
    """Two output rows of one key trade their right payloads, where
    ``dist_join`` returns them: each side's payloads are all there,
    only two are paired with the wrong partner."""
    from cylon_tpu import frame
    from cylon_tpu.column import Column

    dist_join = frame.dist_join

    def wrong_join(*a, **kw):
        out = dist_join(*a, **kw)
        counts = np.asarray(out.nrows)
        cap = out.capacity // len(counts)
        rows = np.concatenate([np.arange(s * cap, s * cap + c)
                               for s, c in enumerate(counts)])
        k, x, y = (np.asarray(out.column(c).data)[rows]
                   for c in ("key", "payload_x", "payload_y"))
        first = {}
        for i in np.argsort(k, kind="stable"):
            j = first.setdefault(k[i], i)
            if x[i] != x[j] and y[i] != y[j]:
                break
        c = out.column("payload_y")
        data = c.data.at[rows[[i, j]]].set(y[[j, i]])
        return out.add_column("payload_y", Column(data, c.validity, c.dtype,
                                                  c.dictionary))

    frame.dist_join = wrong_join


def _half():
    """Half of the left side's rows left out on the way into the
    exchange (``dist_join`` sends the left side first)."""
    from cylon_tpu.parallel import dist_ops

    shuffle_local = dist_ops.shuffle_local
    calls = [0]

    def half_shuffle(table, *a, **kw):
        calls[0] += 1
        if calls[0] % 2:
            table = table.with_nrows(table.nrows // 2)
        return shuffle_local(table, *a, **kw)

    dist_ops.shuffle_local = half_shuffle


def _no_exchange():
    """The exchange between chips left out: every chip joins the rows
    it already holds."""
    from cylon_tpu.parallel import dist_ops

    def local_only(table, pid, out_cap, *a, **kw):
        return table.with_capacity(out_cap)

    dist_ops.shuffle_local = local_only


FAULTS = {"altered": _altered, "paired": _paired, "half": _half,
          "no_exchange": _no_exchange}
