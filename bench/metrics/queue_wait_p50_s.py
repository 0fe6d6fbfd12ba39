"""Median time a query waited in the scheduler's queue: the program's
``queue_wait`` telemetry spans (submit to scheduler pickup) that started
in the window."""
import statistics


def read(window):
    waits = [s.duration_s for s in window.spans
             if s.name == "queue_wait" and window.t0 <= s.t_start < window.t1]
    return statistics.median(waits) if waits else None
