"""Spans reconstructed per second: every span of every round in the
window over the window's whole length (host clock)."""


def read(m):
    return m["spans_done"] / m["window_s"]
