"""The ragged exchange path, run on the CPU mesh.

XLA:CPU cannot execute ``ragged-all-to-all``, so these tests swap in a
reference implementation of its semantics (all-gather + masked copy)
and force ``CYLON_TPU_SHUFFLE=ragged``: everything around the
collective — the 128-word chunk padding of each peer's block, the
offsets, the compaction after receipt — runs exactly as on the chip.
``tests/test_tpu_compile.py`` shows the TPU compiler keeps the real
collective; ``chip_smoke.py`` runs it on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from cylon_tpu.context import WORKER_AXIS
from cylon_tpu.parallel.shuffle import exchange_arrays


def _reference_ragged_all_to_all(operand, output, input_offsets,
                                 send_sizes, output_offsets, recv_sizes,
                                 *, axis_name):
    """``lax.ragged_all_to_all`` semantics: sender s's block for me,
    ``operand_s[input_offsets_s[me]:][:send_sizes_s[me]]``, lands at
    ``output[output_offsets_s[me]:]``."""
    del recv_sizes
    me = jax.lax.axis_index(axis_name)
    ops = jax.lax.all_gather(operand, axis_name)
    ino, snd, outo = (jax.lax.all_gather(x, axis_name)[:, me]
                      for x in (input_offsets, send_sizes, output_offsets))
    r = jnp.arange(output.shape[0])
    out = output
    for s in range(ops.shape[0]):
        inside = (r >= outo[s]) & (r < outo[s] + snd[s])
        idx = jnp.clip(ino[s] + r - outo[s], 0, operand.shape[0] - 1)
        out = jnp.where(inside.reshape((-1,) + (1,) * (out.ndim - 1)),
                        ops[s][idx], out)
    return out


@pytest.fixture
def ragged(monkeypatch):
    monkeypatch.setenv("CYLON_TPU_SHUFFLE", "ragged")
    monkeypatch.setattr(jax.lax, "ragged_all_to_all",
                        _reference_ragged_all_to_all)


@pytest.mark.parametrize("w,width", [(1, 0), (4, 0), (4, 11), (8, 3)])
def test_ragged_exchange_delivers_every_row_once(ragged, rng, w, width):
    """Rows of i64 keys, f64 values and (optionally) a ``width``-word
    matrix reach the shard their pid names, grouped by sender in each
    sender's order — checked against a host-side replay."""
    mesh = Mesh(np.array(jax.devices()[:w]), (WORKER_AXIS,))
    cap = 300                      # per shard; not a multiple of 128
    n = rng.integers(cap // 2, cap + 1, w).astype(np.int32)
    keys = rng.integers(-2**40, 2**40, w * cap).astype(np.int64)
    vals = rng.normal(size=w * cap)
    pid = rng.integers(0, w, w * cap).astype(np.int32)
    arrays = [jnp.asarray(keys), jnp.asarray(vals)]
    if width:
        arrays.append(jnp.asarray(
            rng.integers(0, 2**32, (w * cap, width), dtype=np.uint32)))
    out_cap = 2 * cap

    def body(pid, n, *arrs):
        outs, n_recv = exchange_arrays(list(arrs), pid, n[0], out_cap)
        return (*outs, n_recv.reshape(1))

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(WORKER_AXIS),) * (2 + len(arrays)),
        out_specs=(P(WORKER_AXIS),) * (1 + len(arrays))))
    *outs, n_recv = fn(jnp.asarray(pid), jnp.asarray(n), *arrays)
    host = [np.asarray(a) for a in arrays]
    outs = [np.asarray(o) for o in outs]
    n_recv = np.asarray(n_recv)
    for d in range(w):
        want = [np.concatenate([
            a[s * cap:s * cap + n[s]][pid[s * cap:s * cap + n[s]] == d]
            for s in range(w)]) for a in host]
        assert n_recv[d] == len(want[0])
        for o, x in zip(outs, want):
            got = o[d * out_cap:d * out_cap + n_recv[d]]
            np.testing.assert_array_equal(got, x)
            assert not o[d * out_cap + n_recv[d]:(d + 1) * out_cap].any()
