"""Device-to-host reads the host interpreter waited on, per query: the
program's ``EngineStats.host_syncs`` summed over the window's batches,
divided by the queries they answered. A program without the counter reads
nothing."""


def read(window):
    batches = window.batches
    if not batches or not window.queries:
        return None
    counts = [getattr(s, "host_syncs", None) for s in batches]
    if None in counts:
        return None
    return sum(counts) / len(window.queries)
