"""Seconds of first-touch compilation inside the window: the program's
``EngineStats.compile_time_s`` (wall time of each executable's first call
in the process) summed over the batches that ran in the window. Reads 0
when the warm-up covered every shape the window used."""


def read(window):
    batches = window.batches
    if not batches:
        return None
    return float(sum(s.compile_time_s for s in batches))
