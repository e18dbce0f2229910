"""Helpers the metric readers share: device time from the reduced trace,
and the chip's peaks."""
from __future__ import annotations

import harness


def traced(run) -> bool:
    """A device trace with a window to read."""
    r = run.reduced
    return r is not None and r.n_devices > 0 and r.window_s > 0


def roofline_pct(run, kernels: list[str], work: tuple[float, float]):
    """Least time of ``work`` (operations, needed bytes) at the chip's
    peaks, as a share of the kernels' device time; None where the trace
    shows no call of them.  The least time of the window's summed work
    is at most the sum of each call's, so this never overstates."""
    if not traced(run):
        return None
    t = sum(run.reduced.kernels.get(k, [0.0, 0])[0] for k in kernels)
    if t <= 0 or work[0] <= 0:
        return None
    pk = harness.peaks(run.device.device_kind)
    least = max(work[0] / pk["bf16_flops"], work[1] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t


def mfu_pct(run, flops: float):
    """Needed operations over the window, as a share of the chip's bf16
    peak for the window's length."""
    if not traced(run) or flops <= 0:
        return None
    pk = harness.peaks(run.device.device_kind)
    return 100.0 * flops / (run.reduced.window_s * pk["bf16_flops"] *
                            run.cell.spec["chips"])


def idle_pct(run):
    if not traced(run):
        return None
    return 100.0 * run.reduced.idle_share
