"""Share of device idle time over the traced rounds whose innermost host
span is ``bench:round`` or the program's ``tw:fleet:solve``: idle time
no program stage names (``benchmark/idle_attribution.py``)."""

from benchmark import idle_attribution


def read(m):
    return idle_attribution.unnamed_idle_pct(m)
