"""Device idle ms per 1000 traced spans while the host was in the
program's ``tw:fleet:window-plan``, ``tw:fleet:group`` or
``tw:fleet:pack`` stage (``benchmark/idle_attribution.py``): the part of
the code ``pack_s`` bills that left the chip waiting."""

from benchmark import idle_attribution


def read(m):
    return idle_attribution.stage_idle_ms_per_kspan(
        m, ["window-plan", "group", "pack"])
