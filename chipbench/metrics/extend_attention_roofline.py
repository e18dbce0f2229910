"""The extend kernel's least time (live-context attention of the prompt
chunks dispatched in the window, at the chip's peaks) over its device
time in the window."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "extend_attention_roofline", "%", "kernels", "device_trace", "ttft_p85_ms")

from _common import roofline_pct  # noqa: E402


def read(run):
    return roofline_pct(run, ["extend_attention"],
                        run.work.get("extend_attention", (0.0, 0.0)))
