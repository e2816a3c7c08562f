"""Host milliseconds per join that ``dist_join`` spends dispatching its
program: the program's ``dist_join.dispatch`` span over the traced
window (trace, lowering and enqueue of the one partition, exchange and
local-join program, again at each capacity regrow), per join."""


def read(r):
    n, seconds = r.span("dist_join.dispatch")
    return seconds / r.units * 1e3 if n and r.units else None
