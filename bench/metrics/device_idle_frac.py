"""device_idle_frac: share of the traced window in which no op ran on
the device (1 − union of op intervals / window), mean over chips."""


def read(summary):
    if summary.window_s <= 0 or summary.busy_s <= 0:
        return None
    return 1.0 - summary.busy_s / summary.window_s
