"""The decode kernel's least time (each row's live KV, ``pos + 1``
positions, never the padded slot) over its device time in the window."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "decode_attention_roofline", "%", "kernels", "device_trace", "itl_p90_ms")

from _common import roofline_pct  # noqa: E402


def read(run):
    return roofline_pct(run, ["decode_attention"],
                        run.work.get("decode_attention", (0.0, 0.0)))
