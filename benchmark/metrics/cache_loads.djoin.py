"""Programs loaded from the persistent compilation cache per join in
the traced window, as JAX reports them. ``dist_join`` builds a new
program object on every call, so each join looks its program up again
(ROADMAP C6); a program built once reads 0."""


def read(r):
    if r.cache_loads is None or not r.units:
        return None
    return r.cache_loads / r.units
