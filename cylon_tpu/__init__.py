"""cylon_tpu — a TPU-native distributed dataframe / relational-algebra engine.

A ground-up rebuild of the capabilities of Cylon (reference:
``cpp/src/cylon/table.hpp``, ``python/pycylon/frame.py``) designed for
TPUs: tables live in HBM as struct-of-column device arrays, relational
kernels are XLA/Pallas programs built around sorts and segment
reductions (MXU/VPU friendly, static shapes), and distribution is SPMD
over a ``jax.sharding.Mesh`` with XLA collectives on ICI — replacing
the reference's MPI/UCX channel + async all-to-all stack
(``cpp/src/cylon/net/``).

Public surface mirrors PyCylon:

- :class:`cylon_tpu.table.Table` — columnar table (reference
  ``cpp/src/cylon/table.hpp:46``)
- :class:`cylon_tpu.context.CylonEnv` — execution context / device mesh
  (reference ``python/pycylon/frame.py:88``)
- :class:`cylon_tpu.frame.DataFrame` — pandas-like facade (reference
  ``python/pycylon/frame.py:183``)
- ``cylon_tpu.ops`` — local relational kernels (join/groupby/sort/...)
- ``cylon_tpu.parallel`` — mesh, shuffle, collectives
"""

import os as _os

import jax as _jax

# Tabular data is int64/float64-shaped (reference benchmarks and the whole
# pycylon surface assume 64-bit keys); without x64 JAX silently downcasts.
# Opt out with CYLON_TPU_NO_X64=1 for bf16/int32-only pipelines.
if not _os.environ.get("CYLON_TPU_NO_X64"):
    _jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: relational programs are large (a
# distributed join is one fused shard_map program) and TPU compiles are
# minutes cold — but byte-identical across processes, so cache them on
# disk. JAX_COMPILATION_CACHE_DIR (read by JAX itself) places the cache
# from outside; otherwise it lives at a FIXED path in the checkout —
# the path is part of the cache key, so it must not move between runs.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

from cylon_tpu.utils.logging import init_logging as _init_logging

# CYLON_LOG_LEVEL -> logger config (parity: pycylon/__init__.py:30-43)
_init_logging()

from cylon_tpu import dtypes
from cylon_tpu.column import Column
from cylon_tpu.config import (
    CSVReadOptions,
    CSVWriteOptions,
    JoinAlgorithm,
    JoinConfig,
    JoinType,
    ParquetOptions,
    SortOptions,
)
from cylon_tpu.context import CylonEnv, TPUConfig, LocalConfig
from cylon_tpu.errors import (
    CylonError,
    Code,
    DataLossError,
    DeadlineExceeded,
    FailedPrecondition,
    IndexError_,
    InvalidArgument,
    KeyError_,
    NotImplemented_,
    OutOfCapacity,
    ResourceExhausted,
    TransientError,
    TypeError_,
)
from cylon_tpu.config import DeadlinePolicy, RetryPolicy
from cylon_tpu import telemetry
from cylon_tpu import fallback
from cylon_tpu import pipeline
from cylon_tpu.resilience import FaultPlan, FaultRule
from cylon_tpu.watchdog import deadline
from cylon_tpu.table import Table
from cylon_tpu.series import Series
from cylon_tpu.frame import DataFrame, GroupByDataFrame, concat, merge, read_csv
from cylon_tpu.io import (read_csv_chunks, read_csv_sharded,
                          read_parquet_chunks, write_csv_sharded)
from cylon_tpu.indexing import IndexingType

__version__ = "0.1.0"

__all__ = [
    "Column",
    "CSVReadOptions",
    "CSVWriteOptions",
    "ParquetOptions",
    "CylonEnv",
    "CylonError",
    "Code",
    "DataLossError",
    "DeadlineExceeded",
    "DeadlinePolicy",
    "FailedPrecondition",
    "FaultPlan",
    "FaultRule",
    "ResourceExhausted",
    "RetryPolicy",
    "deadline",
    "TransientError",
    "IndexError_",
    "InvalidArgument",
    "JoinAlgorithm",
    "JoinConfig",
    "JoinType",
    "KeyError_",
    "LocalConfig",
    "NotImplemented_",
    "OutOfCapacity",
    "SortOptions",
    "DataFrame",
    "GroupByDataFrame",
    "IndexingType",
    "Series",
    "Table",
    "TPUConfig",
    "TypeError_",
    "concat",
    "dtypes",
    "merge",
    "read_csv",
    "read_csv_chunks",
    "read_csv_sharded",
    "pipeline",
    "read_parquet_chunks",
    "telemetry",
    "write_csv_sharded",
]
