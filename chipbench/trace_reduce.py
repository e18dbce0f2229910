"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The device planes (``/device:TPU:<n>``) carry two lines this reads:
``XLA Modules``, one event per executed program (``jit_<function>(<id>)``),
and ``XLA Ops``, one event per operation inside it; a Pallas kernel shows
there as its custom call, named after the jitted function that wraps the
``pallas_call`` (``%decode_attention_streams.4 = ... custom-call(...)``).
The host plane (``/host:CPU``) carries the benchmark's own spans
(``cb.*``), among them ``cb.window`` around the measured window.

* busy time: the union of the program intervals inside the window,
  averaged over the device planes; the idle share is 1 - busy / window;
* device time and count per jitted step and per kernel, matched by the
  name prefixes the cell file lists under ``trace_names``;
* the operations that took most time, and the longest idle gaps, each
  labelled by the innermost benchmark span open on the host at the time.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "cb.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=.*)?$", re.S)


def op_name(event_name: str) -> str:
    """``%decode_attention_streams.4 = bf16[...] custom-call(...)`` ->
    ``decode_attention_streams``; ``jit_decode_step(8968)`` -> ``jit_decode_step``."""
    head = event_name.split(" = ", 1)[0].split("(", 1)[0].strip()
    m = _OP_NAME.match(head)
    return m.group(1) if m else head


def _union(intervals):
    total = 0
    cur_lo = cur_hi = None
    out = []
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                out.append((cur_lo, cur_hi))
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        out.append((cur_lo, cur_hi))
        total += cur_hi - cur_lo
    return total, out


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    #: name -> [device seconds, calls] for each step and kernel of trace_names
    steps: dict = field(default_factory=dict)
    kernels: dict = field(default_factory=dict)
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10], "idle_gaps": self.idle_gaps[:10]}


def _matches(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p) for p in prefixes)


def reduce_planes(planes, names: dict) -> Reduced:
    """Reduce ``jax.profiler.ProfileData`` planes (or objects with the same
    ``name``/``lines``/``events`` shape)."""
    host_spans = []
    devices = []
    for pl in planes:
        if pl.name.startswith("/device:TPU:") or pl.name.startswith("/device:GPU:"):
            devices.append(pl)
        elif pl.name.startswith("/host:"):
            for line in pl.lines:
                for e in line.events:
                    if e.name.startswith("cb."):
                        host_spans.append((e.start_ns, e.end_ns, e.name))
    win = [s for s in host_spans if s[2] == WINDOW_SPAN]
    spans = [s for s in host_spans if s[2] != WINDOW_SPAN]
    steps = {k: [0.0, 0] for k in names.get("steps", {})}
    kernels = {k: [0.0, 0] for k in names.get("kernels", {})}
    op_tot: dict = {}
    busy_total = 0.0
    busy_iv: list = []
    w_lo = w_hi = None
    if win:
        w_lo, w_hi = win[0][0], win[0][1]
    for dev in devices:
        lines = {ln.name: list(ln.events) for ln in dev.lines}
        mods = lines.get("XLA Modules", [])
        if w_lo is None and mods:
            w_lo = min(e.start_ns for e in mods)
            w_hi = max(e.end_ns for e in mods)
        if w_lo is None:
            continue

        def inside(e):
            return e.end_ns > w_lo and e.start_ns < w_hi

        iv = [(max(e.start_ns, w_lo), min(e.end_ns, w_hi)) for e in mods
              if inside(e)]
        busy, merged = _union(iv)
        busy_total += busy
        busy_iv.extend(merged)
        for e in mods:
            if not inside(e):
                continue
            nm = op_name(e.name)
            for k, pre in names.get("steps", {}).items():
                if _matches(nm, pre):
                    steps[k][0] += e.duration_ns * 1e-9
                    steps[k][1] += 1
        for e in lines.get("XLA Ops", []):
            if not inside(e):
                continue
            nm = op_name(e.name)
            op_tot[nm] = op_tot.get(nm, 0.0) + e.duration_ns * 1e-9
            for k, pre in names.get("kernels", {}).items():
                if _matches(nm, pre):
                    kernels[k][0] += e.duration_ns * 1e-9
                    kernels[k][1] += 1
    n = max(len(devices), 1)
    window_s = (w_hi - w_lo) * 1e-9 if w_lo is not None else 0.0
    gaps = []
    if devices and w_lo is not None:
        _, merged = _union(busy_iv)
        edges = [w_lo] + [x for iv in merged for x in iv] + [w_hi]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((lo, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for lo, hi in gaps[:10]:
        mid = (lo + hi) / 2
        open_ = [s for s in spans if s[0] <= mid <= s[1]]
        label = min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "no span"
        labelled.append([label, (hi - lo) * 1e-9])
    top = sorted(op_tot.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=window_s, busy_s=busy_total * 1e-9 / n,
                   n_devices=len(devices), steps=steps, kernels=kernels,
                   top_ops=[[k, v] for k, v in top], idle_gaps=labelled)


def find_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_file(path, names: dict) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes, names)


def reduce_dir(trace_dir, names: dict) -> Reduced:
    return reduce_file(find_xplane(trace_dir), names)
