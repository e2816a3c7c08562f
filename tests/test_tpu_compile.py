"""The main path's kernels compile for a v5e chip that is described, not
attached.

The TPU compiler is installed here: ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and ``jit(...).lower(...).compile()``
raises what the chip's compiler would raise (Mosaic refusals, VMEM
overuse, unlowerable collectives). Interpret mode cannot see these.
The TPU code paths are chosen with ``on_platform("tpu")``: this process
itself runs on the CPU.

The topology is described inside a module fixture, never at import:
pytest-xdist workers then all collect the same tests. Without libtpu
the tests skip; any other failure to describe the chip fails them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import cylon_tpu  # noqa: F401  (x64 init)
from cylon_tpu.context import WORKER_AXIS
from cylon_tpu.ops import pallas_kernels as pk
from cylon_tpu.platform import on_platform

BIG = 1 << 23        # rows: a 8M-row column, SF1 lineitem scale


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    with on_platform("tpu"):
        return jax.jit(fn).lower(*shapes).compile()


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_row_hash_compiles(one_chip):
    w = jax.ShapeDtypeStruct((BIG,), jnp.uint32, sharding=one_chip)
    c = _compile(lambda a, b: pk.row_hash([a, b], 4), w, w)
    assert _kernel_in(c)


@pytest.mark.parametrize("kind,dtype", [("add", jnp.int32),
                                        ("max", jnp.int32)])
def test_scan32_compiles(one_chip, kind, dtype):
    x = jax.ShapeDtypeStruct((BIG,), dtype, sharding=one_chip)
    assert _kernel_in(_compile(lambda a: pk.scan32(a, kind), x))


def test_pair_max_scan_compiles(one_chip):
    x = jax.ShapeDtypeStruct((BIG,), jnp.uint32, sharding=one_chip)
    assert _kernel_in(_compile(pk.pair_max_scan, x, x))


def test_bucket_build_compiles(one_chip):
    bids = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip)
    c = _compile(lambda b: pk.bucket_build(b, 1 << 14, 4), bids)
    assert _kernel_in(c)


def test_bucket_probe_compiles(one_chip):
    n, nb, width = 1 << 20, 1 << 14, 4
    pbids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    pw = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((width, nb), jnp.int32, sharding=one_chip)
    bw = jax.ShapeDtypeStruct((nb,), jnp.uint32, sharding=one_chip)
    c = _compile(lambda p, a, b, t, x, y: pk.bucket_probe(p, [a, b], t,
                                                          [x, y]),
                 pbids, pw, pw, table, bw, bw)
    assert _kernel_in(c)


def test_exchange_compiles_to_ragged_all_to_all(topo):
    """A 4-chip exchange of int64 keys + f64 payloads: the ragged
    all-to-all (over the 32-bit transport words) is what the compiler
    keeps."""
    from cylon_tpu.parallel.shuffle import exchange_arrays

    mesh = Mesh(topo.devices[:4], (WORKER_AXIS,))
    rows = 1 << 20                       # per shard
    row = NamedSharding(mesh, P(WORKER_AXIS))

    def body(k, v, pid, n):
        (k2, v2), n_recv = exchange_arrays([k, v], pid, n[0], 2 * rows)
        return k2, v2, n_recv.reshape(1)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(WORKER_AXIS),) * 4,
                       out_specs=(P(WORKER_AXIS),) * 3)
    shapes = [jax.ShapeDtypeStruct((4 * rows,), dt, sharding=row)
              for dt in (jnp.int64, jnp.float64, jnp.int32)]
    n = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=row)
    compiled = _compile(fn, *shapes, n)
    assert "ragged-all-to-all" in compiled.as_text()
    # rows travel as 128-word chunks: a [rows, 4] word operand is laid
    # out one 128-lane tile per row (1.61 GB temp here, 1.08 GB chunked)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9
