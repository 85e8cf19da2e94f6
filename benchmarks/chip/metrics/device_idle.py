"""Share of the traced window, in %, in which no operation ran on the
device (averaged over the chips).  Read as ``device_idle.<part>``, one
name per end-to-end metric it moves."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
