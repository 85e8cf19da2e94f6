"""Probe windows walked per key by the fused retrieval walk
(``TableStats`` probe_sum / probe_n of one lookup call)."""


def read(run):
    stats = run["stats"]
    if run["op"] != "retrieve" or stats is None or not stats["probe_n"]:
        return None
    return stats["probe_sum"] / stats["probe_n"]
