"""The three statistics kernels' summed least time (needed row bytes at
the chip's bandwidth, or operations at its peak) over their summed
device time in the window."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "analytics_kernels_roofline", "%", "kernels", "device_trace", "fits_per_s")

from _common import roofline_pct  # noqa: E402


def read(run):
    return roofline_pct(run, ["linreg_stats", "nb_stats", "logreg_sgd"],
                        run.work.get("analytics_kernels", (0.0, 0.0)))
