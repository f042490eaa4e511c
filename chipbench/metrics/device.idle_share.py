"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, averaged over chips."""
LAYER = "device"
SOURCE = "device_trace"
MOVES = "ops_per_s"
UNIT = "%"


def read(window):
    tr = window.trace
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * tr.idle_share
