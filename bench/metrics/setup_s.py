"""Seconds from process start to the window's first fetch or request:
interpreter and JAX start-up, corpus generation and encode, container
write, store open, compile-cache loads and warm-up."""


def read(m):
    return m["setup_s"]
