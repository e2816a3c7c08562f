"""Shared example bootstrap: a virtual 8-device CPU mesh by default (the
reference's `mpirun --oversubscribe` analog). Set CYLON_EXAMPLES_TPU=1
to run on real chips instead — kept opt-in because a process that
initialises the TPU backend holds the chip until it exits."""

import os
import sys

# runnable from a source checkout without installing
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup():
    import jax

    if os.environ.get("CYLON_EXAMPLES_TPU"):
        return jax
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    jax.config.update("jax_platforms", "cpu")
    return jax
