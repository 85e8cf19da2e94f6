"""Share of the HBM roofline, in %: the byte floor of every unit in the
traced window (``bytes.py``: a build's or a lookup's, as the cell's op is)
over peak HBM bandwidth times the device's busy time, summed over the
chips.  Read as ``hbm_share.<part>``, one name per end-to-end metric it
moves."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    peak = run["peak"]["hbm_bytes_per_s"]
    return 100.0 * run["floor_bytes"] / (peak * trace["busy_s"] * run["chips"])
