"""The stream's share of the HBM roofline, in percent: the least time
the chip needs to read every column each query reads once, at its
least width (``least_bytes`` per pass, from the cell's shapes, at the
peak HBM bandwidth), over the device's busy time for those passes."""


def read(r):
    if r.trace is None or not r.trace.busy_s or not r.units:
        return None
    least_s = r.units * r.least_bytes / (r.chips * r.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / r.trace.busy_s
