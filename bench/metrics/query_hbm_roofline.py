"""Share of the HBM roofline that the device work of the window reaches,
in percent: the least bytes the window's queries must move
(``bench/work/<algorithm>.py``) over the chip's peak HBM bandwidth
(``bench/peaks.json``) is the least time the chip could take; divided by
the device's busy time in the trace."""


def read(window):
    if window.trace is None or not window.work_bytes:
        return None
    busy = window.trace["busy_s"]
    if busy <= 0:
        return None
    return 100.0 * window.work_bytes / window.peak["hbm_bytes_per_s"] / busy
