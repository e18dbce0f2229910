"""Share of the window in which no program ran on the chip."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "device_idle_share.analytics", "%", "device", "device_trace", "fits_per_s")

from _common import idle_pct  # noqa: E402


def read(run):
    return idle_pct(run)
