"""The exchange's share of the interconnect's roofline, in percent: the
least time a chip needs to send the rows that must leave it
(``least_ici_bytes`` per join, from the cell's shapes, at the chip's
peak interconnect bandwidth) over the device seconds of the
``cylon.exchange/all-to-all`` stage per join, averaged over the
chips."""


def read(r):
    if r.trace is None or not r.units or not r.least_ici_bytes:
        return None
    stage = sum(s for (op, st, _), s in r.trace.scopes.items()
                if op == "cylon.exchange" and st == "all-to-all")
    if not stage:
        return None
    least_s = r.units * r.least_ici_bytes / (r.peaks["ici_bits_per_s"] / 8)
    return 100.0 * least_s / stage
