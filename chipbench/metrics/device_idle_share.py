"""device_idle_share: the share of the traced window in which no
operation ran on the device, 1 - (union of device-op intervals) / window,
in percent, averaged over the cell's chips."""


def read(run):
    t = run.trace
    if t is None or t["chips"] == 0 or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
