"""A throwaway per-layer metric for the tests: fetches in the window."""


def read(m):
    return m.get("fetches")
