"""Device milliseconds per pass of the filters (the ``cylon.filter``
scope: their masks and compactions), from the trace's operator scopes,
averaged over the chips."""

import trace_scopes


def read(r):
    return trace_scopes.per_unit_ms(r, "cylon.filter")
