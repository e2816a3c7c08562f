"""Which platform will the next computation run on?

Several trace-time dispatch decisions depend on the *execution*
platform: Pallas kernels compile only on TPU (``ops/pallas_kernels``),
and ``lax.ragged_all_to_all`` is unimplemented on XLA:CPU
(``parallel/shuffle``). ``jax.default_backend()`` answers the wrong
question whenever a TPU is visible but the computation targets a CPU
mesh — exactly the driver's ``dryrun_multichip`` configuration, and the
round-1 gate failure. The distributed ops therefore pin the ambient
platform to their mesh's device platform while tracing; local paths
fall back to ``jax_default_device``'s platform, then the default
backend.
"""

import contextlib
import contextvars
import functools

import jax

_PLATFORM: contextvars.ContextVar = contextvars.ContextVar(
    "cylon_platform", default=None)


@contextlib.contextmanager
def on_platform(platform: str):
    """Pin dispatch decisions to ``platform`` for the duration (used
    around shard_map tracing by the distributed ops)."""
    tok = _PLATFORM.set(platform)
    try:
        yield
    finally:
        _PLATFORM.reset(tok)


def current_platform() -> str:
    p = _PLATFORM.get()
    if p:
        return p
    d = jax.config.jax_default_device
    if d is not None:
        return getattr(d, "platform", str(d))
    return jax.default_backend()


def platform_jit(fn=None, *, static_argnames=()):
    """``jax.jit`` with the ambient platform folded into the trace-cache
    key.

    Platform-sensitive dispatch (Pallas on/off, the f64 bit-extraction
    route in ``kernels.float_bits``) happens at *trace* time, but jit's
    cache is keyed only on avals + static args — a jaxpr traced for one
    platform would silently be reused for another. Every module-level
    jitted operator that can make such a decision goes through this
    wrapper instead of ``jax.jit``. The program keeps ``fn``'s name
    (``jit(_join_compiled)``), so a device trace says which operator
    ran.
    """
    if fn is None:
        return functools.partial(platform_jit,
                                 static_argnames=static_argnames)

    def keyed(_pk, *args, **kwargs):
        del _pk  # cache key only; dispatch reads the ambient platform
        return fn(*args, **kwargs)

    # the name only: functools.wraps would also hand jit fn's signature
    # (through __wrapped__), against which static_argnums=(0,) is wrong
    keyed.__name__ = keyed.__qualname__ = fn.__name__

    jitted = jax.jit(keyed, static_argnums=(0,),
                     static_argnames=tuple(static_argnames))

    @functools.wraps(fn)
    def run(*args, **kwargs):
        return jitted(current_platform(), *args, **kwargs)

    return run
