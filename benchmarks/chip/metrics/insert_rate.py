"""Keys inserted per second: every key of every whole build in the window
over the window's wall time."""


def read(run):
    if run["op"] != "insert":
        return None
    w = run["window"]
    return w["keys"] / w["elapsed_s"]
