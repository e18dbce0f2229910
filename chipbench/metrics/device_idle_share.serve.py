"""Share of the serving window in which no program ran on the chip."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "device_idle_share.serve", "%", "device", "device_trace", "itl_p90_ms")

from _common import idle_pct  # noqa: E402


def read(run):
    return idle_pct(run)
