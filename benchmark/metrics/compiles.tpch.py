"""Query programs the planner built inside the traced window: the
change of the program's ``plan.compile_count``. Every shape is warmed
in set-up, so this reads 0; more means the window paid a compile."""


def read(r):
    if not any(d["name"] == "plan.compile_count" for d in r.delta.values()):
        return None
    return r.counter("plan.compile_count")
