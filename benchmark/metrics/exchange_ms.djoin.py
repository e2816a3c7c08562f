"""Device milliseconds per join of the exchange (the ``cylon.exchange``
scope, all four stages: partition, pack, all-to-all and unpack, for
both sides), from the trace's operator scopes, averaged over the
chips."""

import trace_scopes


def read(r):
    return trace_scopes.per_unit_ms(r, "cylon.exchange")
