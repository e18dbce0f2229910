"""Device time of one batched decode step (``jit_decode_step``), the
window's total over its calls."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "decode_step_ms.serve", "ms", "model steps", "device_trace", "itl_p90_ms")

from _common import traced  # noqa: E402


def read(run):
    if not traced(run):
        return None
    t, calls = run.reduced.steps.get("decode_step", [0.0, 0])
    return 1e3 * t / calls if calls else None
