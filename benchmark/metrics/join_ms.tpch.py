"""Device milliseconds per pass of the local sort joins (the
``cylon.join`` scope: keys, sort, match, order and gather), from the
trace's operator scopes, averaged over the chips. In the distributed
join's cell, a pass is one join and this is the local join that every
chip runs on the rows the exchange brought it."""

import trace_scopes


def read(r):
    return trace_scopes.per_unit_ms(r, "cylon.join")
