"""Fleet dispatch groups launched per 1000 spans (the program's
``fleet_dispatches`` counter over the window's rounds)."""


def read(m):
    n = m["stats"].get("fleet_dispatches", 0.0)
    return n / (m["spans_done"] / 1000.0) if n else None
