"""Device idle ms per 1000 traced spans while the host was in the
program's ``tw:fleet:decode`` stage (``benchmark/idle_attribution.py``):
the part of ``decode_s`` that left the chip waiting."""

from benchmark import idle_attribution


def read(m):
    return idle_attribution.stage_idle_ms_per_kspan(m, ["decode"])
