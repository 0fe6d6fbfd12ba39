"""Share of the window in which no operation ran on the device, in
percent: 1 - busy / window, from the profiler trace, averaged over the
chips used."""


def read(window):
    if window.trace is None or window.trace["window_s"] <= 0:
        return None
    t = window.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
