"""Device milliseconds per pass of the groupbys (the ``cylon.groupby``
scope: sort, permute and reduce), from the trace's operator scopes,
averaged over the chips."""

import trace_scopes


def read(r):
    return trace_scopes.per_unit_ms(r, "cylon.groupby")
