"""Device milliseconds of sort operations per pass of the stream, from the
trace, averaged over the chips."""


def read(r):
    if r.trace is None or not r.units:
        return None
    seconds = r.trace.op_seconds(r"^sort")
    return seconds / r.units * 1e3 if seconds else None
