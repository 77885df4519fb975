"""device_idle_share (%, device trace): one minus the union of the device
op intervals over the traced window, averaged over the cell's chips."""


def read(record):
    tr = record["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
