"""device_roofline_share: the least HBM traffic a decoded SvS needs for
the answered queries (the reference's count: shortest list, every
candidate probed, the answer; 4 bytes each) over what the chip's
bandwidth moves in the device's busy time."""


def read(run):
    tr = run.trace
    if not tr or not tr["busy_s"] or not run.least_bytes or not run.peaks:
        return None
    return 100.0 * run.least_bytes / (run.peaks["hbm_bytes_per_s"]
                                      * tr["busy_s"])
