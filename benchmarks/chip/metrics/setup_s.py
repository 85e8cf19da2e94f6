"""Seconds from process start to the first timed unit: device start-up,
compile (from the cache after a checkout's first run), inputs made on the
device, the table built for lookup cells, and one warm-up unit."""


def read(run):
    return run["setup_s"]
