"""Device busy time per 1000 spans over the traced rounds: the union
of the TPU's operation intervals (``benchmark/trace.py``)."""


def read(m):
    t = m["trace"]
    if not t or not m["traced_spans"]:
        return None
    return 1000.0 * t["busy_s"] / (m["traced_spans"] / 1000.0)
