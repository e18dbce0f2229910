"""The window's needed operations (every prompt token computed and every
token decoded through the weights and the head, plus attention over each
token's live context) over the window, as a share of the chip's bf16
peak: the whole serving step, which bounds both kernels' shares."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "mfu.serve", "%", "serving step", "device_trace", "itl_p90_ms")

from _common import mfu_pct  # noqa: E402


def read(run):
    return mfu_pct(run, run.work.get("step_ops", (0.0, 0.0))[0])
