"""Host milliseconds per fetch in the stream's container I/O (background
ranged reads, CRC and codec checks) and residency upload stages, from the
stream's own stage clocks over the window."""


def read(m):
    c = m["counters"]
    if not c.get("fetches"):
        return None
    return 1e3 * (c["io_seconds"] + c["upload_seconds"]) / c["fetches"]
