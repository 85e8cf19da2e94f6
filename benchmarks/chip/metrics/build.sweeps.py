"""Virtual-fill arbitration sweeps of one build (``TableStats.
fixpoint_iters`` from a ``stats=True`` build on the same inputs)."""


def read(run):
    stats = run["stats"]
    if run["op"] != "insert" or stats is None:
        return None
    return stats["fixpoint_iters"]
