"""Runtime utilities: logging and op tracing.

Reference analog: ``cpp/src/cylon/util/`` (logging.{hpp,cpp} glog wrap,
macros) plus the inline ``std::chrono`` op timing at table boundaries
(``table.cpp:167-177``).
"""

from cylon_tpu.utils.logging import (disable_logging, get_logger,
                                     init_logging, log_level)
from cylon_tpu.utils.tracing import (report, reset_timings, scope, span,
                                     timings, traced)


def pow2_bucket(n: int, minimum: int = 1) -> int:
    """Smallest power of two >= max(n, minimum) — THE capacity bucket
    policy (power-of-2 buckets bound the distinct shape count and hence
    compiles; see plan.capacity_scale)."""
    return max(int(minimum), 1 << max(int(n) - 1, 0).bit_length())


__all__ = [
    "disable_logging", "get_logger", "init_logging", "log_level",
    "pow2_bucket",
    "report", "reset_timings", "scope", "span", "timings", "traced",
]
