"""The fits' needed operations over the window, as a share of the chip's
bf16 peak: the whole analytics step, which bounds the kernels' share."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "mfu.analytics", "%", "analytics engine", "device_trace", "fits_per_s")

from _common import mfu_pct  # noqa: E402


def read(run):
    return mfu_pct(run, run.work.get("analytics_kernels", (0.0, 0.0))[0])
