"""Device-kernel launches per query: the program's
``EngineStats.total_launches`` summed over the window's batches, divided
by the queries they answered (host interpreter: ``core/engine.py``,
``batch/engine.py``)."""


def read(window):
    batches = window.batches
    if not batches:
        return None
    return sum(s.total_launches for s in batches) / len(window.queries)
