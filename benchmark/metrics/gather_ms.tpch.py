"""Device milliseconds per pass of gather operations under any operator
scope (leaf primitive ``gather``: the joins' and groupbys' permutations,
the filters' compactions, the exchange's packing and unpacking), from
the trace's operator scopes, averaged over the chips."""

import trace_scopes


def read(r):
    return trace_scopes.per_unit_ms(r, primitive="gather")
