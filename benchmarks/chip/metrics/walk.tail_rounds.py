"""Rounds of the compacted tail of the probe walk in one lookup call
(``TableStats.tail_rounds``)."""


def read(run):
    stats = run["stats"]
    if run["op"] != "retrieve" or stats is None:
        return None
    return stats["tail_rounds"]
