"""Device busy milliseconds of the traced window per block the stream
delivered in it; blocks are counted from the window's fetch spans."""


def read(m):
    t = m["trace"]
    fetches = len(t.spans.get("bench.fetch", ())) if t else 0
    if not fetches or t.busy_s <= 0:
        return None
    return 1e3 * t.busy_s / (fetches * m["blocks_per_fetch"])
