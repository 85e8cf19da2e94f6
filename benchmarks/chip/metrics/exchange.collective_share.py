"""Share of the device's busy time, in %, spent in collective operations
(the ownership exchange's all-to-all), averaged over the chips."""


def read(run):
    trace = run["trace"]
    if (trace is None or run["chips"] < 2 or trace["busy_s"] <= 0
            or trace["collective_s"] <= 0):    # no collective op found
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]
