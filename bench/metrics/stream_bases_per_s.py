"""Bases of every fetch completed in the window, ready on the device in the
consumer's format, over the window's seconds."""


def read(m):
    if "bases" not in m:
        return None
    return m["bases"] / m["window_s"]
