"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, averaged over the chips."""


def read(m):
    t = m["trace"]
    return None if t is None else 100.0 * t.idle_share
