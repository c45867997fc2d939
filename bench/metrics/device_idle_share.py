"""device_idle_share: share of the traced window in which no operation
ran on the device (1 - union of op intervals / window)."""


def read(run):
    tr = run.trace
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
