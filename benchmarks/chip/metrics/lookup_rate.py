"""Keys retrieved per second: every key of every call in the window over
the window's wall time."""


def read(run):
    if run["op"] != "retrieve":
        return None
    w = run["window"]
    return w["keys"] / w["elapsed_s"]
