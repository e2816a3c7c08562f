"""Host milliseconds the planner takes to dispatch one query: the
program's ``plan.dispatch`` span over the traced window, per call.
On a warm plan cache this is pure host work (argument handling and the
enqueue of the compiled program)."""


def read(r):
    n, seconds = r.span("plan.dispatch")
    return seconds / n * 1e3 if n else None
