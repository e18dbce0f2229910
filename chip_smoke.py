#!/usr/bin/env python
"""Chip smoke: the serving path at deepseek-67b's published widths on one TPU.

One process drives one chip through five phases, in order, each printing
its findings on lines of its own:

  (a) device: kind, count and HBM limit, and the kernel routing the
      backend chose (compiled Pallas, never interpret mode);
  (b) kernel parity: the decode and extend Pallas kernels, compiled, at
      the model's attention widths (64 query heads over 8 KV heads, head
      dim 128, bf16, 4096 KV positions) against their jnp references;
  (c) serve: ``repro.launch.serve.main`` in-process on deepseek-67b cut to
      4 layers (every width and dtype as published), 4 sessions over 2
      shared documents; it must route decode and extend to the kernels,
      reuse stored prefixes and hit segments across sessions;
  (d) logits: first-token logits of a request built from stored segments
      against ``LM.prefill`` over the same prefix, both against that prefill
      computed in f32, then peak device memory;
  (e) the paper's kernels: linear regression, naive Bayes and logistic
      regression fits with ``backend="pallas"`` against ``backend="numpy"``
      at the paper's 5M rows x 10 features (one 8192-row SGD chunk).

Every check raises on failure.  Without a TPU the script exits non-zero
before any phase.  The last line of stdout is one JSON object naming the
device.

Run from the repo root:  python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

SERVE_ARGV = [
    "--arch", "deepseek-67b", "--layers", "4",
    "--sessions", "4", "--shared-docs", "2", "--doc-len", "2048",
    "--requests", "2", "--new-tokens", "16", "--chunk-tokens", "128",
]
#: prefix of the logit check: past the first stored chunks, so the build
#: reuses segments and extends the rest; a multiple of the 512-token
#: attention block keeps the reference prefill on its blocked path
LOGIT_PREFIX = 1024

# Tolerances, each beside its reason.
#: kernel vs f32 reference, as max|Δ| / max|v|.  The output is a convex
#: combination of v rows, so max|v| is its scale.  The kernel rounds its
#: output to bf16 (≤ 2^-9 of that scale) and the MXU may take f32 operands
#: in bf16 passes (another ≤ 2^-9); 1e-2 leaves room for the rounded scores.
KERNEL_TOL = 1e-2
#: first-token logits, as ‖Δ‖₂ / ‖ref‖₂.  The bf16 model's own floor is
#: measured in the same run: the one-shot bf16 ``LM.prefill`` against the
#: same prefill computed in f32 (same bf16 weights, HIGHEST matmuls).  The
#: served path builds the prefix from stored segments and extend-kernel
#: chunks, rounding in another order, so it may sit up to 1.5x as far from
#: the f32 result as the one-shot prefill does; two such bf16 results sit
#: about √2 floors apart, so served vs one-shot prefill may differ by up to
#: 2 floors.  F32_NOISE covers f32 models, whose floor is ~0.
LOGIT_VS_F32 = 1.5
LOGIT_VS_PREFILL = 2.0
F32_NOISE = 1e-4
#: sanity bound on the floor itself: bf16 rounding (2^-9) compounded over 4
#: layers and peaky random-weight attention stays well under this
BF16_FLOOR_MAX = 5e-2
#: pallas (f32) vs numpy (f64) sufficient statistics, as max|Δ| / max|ref|:
#: f32 accumulation over ~10^4 row blocks of 512.
STATS_TOL = 1e-3
#: one SGD epoch, pallas (f32) vs numpy (f64), as max|Δw| / max|w|: the
#: f32 rounding of 128 sequential minibatch updates.
SGD_TOL = 1e-3


def require_tpu():
    """The first device, if it is a TPU; otherwise exit non-zero."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {dev.platform!r}, not a "
                         f"TPU; this smoke runs on the chip only")
    return dev


def _rel(a, b, scale) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(float(np.abs(scale).max()), 1e-30))


def _check(name: str, err: float, tol: float) -> None:
    print(f"  {name}: error {err:.3e} (tol {tol:.3e})")
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} exceeds {tol:.3e}")


def phase_device(dev) -> dict:
    """(a) Device line and kernel routing: compiled kernels, no fallback."""
    from repro.kernels.common import (decode_kernel_mode, extend_kernel_mode,
                                      use_interpret)

    limit = (dev.memory_stats() or {}).get("bytes_limit")
    print(f"(a) device: {dev.device_kind}, {len(jax.devices())} device(s), "
          f"bytes_limit {limit}")
    routes = {"interpret": use_interpret(), "decode": decode_kernel_mode(),
              "extend": extend_kernel_mode()}
    print(f"  routing: pallas interpret={routes['interpret']}, "
          f"decode {routes['decode']}, extend {routes['extend']}")
    if routes != {"interpret": False, "decode": "kernel", "extend": "kernel"}:
        raise AssertionError(f"backend did not choose the compiled kernels: "
                             f"{routes}")
    return {"bytes_limit": limit}


def phase_kernels(*, batch: int = 4, kv: int = 8, group: int = 8,
                  hd: int = 128, t: int = 4096, nb: int = 128,
                  dtype=jnp.bfloat16, interpret: bool = False,
                  seed: int = 0) -> dict:
    """(b) Decode (batch·kv streams) and extend (one sequence, ``nb`` query
    rows) kernels against their jnp references."""
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.extend_attention import ops as extend_ops
    from repro.kernels.extend_attention.ref import extend_attention_ref

    rng = np.random.default_rng(seed)
    h = kv * group

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    q, k, v = arr(batch, 1, h, hd), arr(batch, t, kv, hd), arr(batch, t, kv, hd)
    pos = rng.integers(0, t, batch)
    pos[0] = t - 1                                   # one row spans all of T
    pos = jnp.asarray(pos, jnp.int32)
    out = decode_ops.decode_attention(q, k, v, pos=pos, interpret=interpret)
    with jax.default_matmul_precision("float32"):
        ref = decode_attention_ref(q[:, 0].reshape(batch, kv, group, hd),
                                   k, v, pos)
    print(f"(b) kernel parity, {jnp.dtype(dtype).name}, hd {hd}, "
          f"interpret={interpret}")
    errs = {"decode": _rel(out.reshape(ref.shape), ref, v)}
    _check(f"decode B·KV={batch * kv} T={t} ragged pos", errs["decode"],
           KERNEL_TOL)

    qe, ke, ve = arr(1, nb, h, hd), arr(1, t, kv, hd), arr(1, t, kv, hd)
    t_real = t - nb // 2                             # a padded cache's valid end
    oute = extend_ops.extend_attention(qe, ke, ve, t_real=t_real,
                                       interpret=interpret)
    with jax.default_matmul_precision("float32"):
        refe = extend_attention_ref(
            qe.astype(jnp.float32), jnp.repeat(ke, group, axis=2),
            jnp.repeat(ve, group, axis=2), t_real=t_real)
    errs["extend"] = _rel(oute, refe, ve)
    _check(f"extend G={group} nb={nb} T={t} t_real={t_real}", errs["extend"],
           KERNEL_TOL)
    return errs


def phase_serve(argv: list[str]):
    """(c) Serve in-process; returns the ``SessionManager`` that ran."""
    from repro.launch import serve

    print(f"(c) serve: {' '.join(argv)}", flush=True)
    mgr = serve.main(argv)
    agg = mgr.aggregate_stats()
    hits = mgr.store.cross_session_hits
    print(f"  serve checks: decode {mgr.decode_mode}, extend "
          f"{mgr.extend_mode}, reuse {agg.reuse_frac:.1%} "
          f"({agg.tokens_reused} tokens), cross-session hits {hits}")
    if mgr.decode_mode != "kernel" or mgr.extend_mode != "kernel":
        raise AssertionError(f"serving did not route to the kernels: decode "
                             f"{mgr.decode_mode}, extend {mgr.extend_mode}")
    if agg.tokens_reused <= 0 or hits <= 0:
        raise AssertionError(f"no reuse ({agg.tokens_reused} tokens) or no "
                             f"cross-session hits ({hits})")
    return mgr


def phase_logits(mgr, *, prefix: int = LOGIT_PREFIX) -> dict:
    """(d) Logits of a prefix built from stored segments vs ``LM.prefill``,
    both against the same prefill computed in f32."""
    from repro.models.lm import LM
    from repro.serve.engine import ServeStats

    s = mgr.sessions[0]                  # session 0 serves a shared document
    prefix = min(prefix, len(s.doc))
    stats = ServeStats()
    served, _, plan = mgr.builder.prefix_with_logits(
        s.doc, prefix, doc_id=s.doc_id, stats=stats)
    if stats.tokens_reused <= 0:
        raise AssertionError("the checked prefix reused no stored segment")
    batch = {"tokens": jnp.asarray(s.doc[None, :prefix])}
    one_shot, _ = jax.jit(mgr.model.prefill)(mgr.params, batch)
    f32_model = LM(dataclasses.replace(mgr.model.cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        f32, _ = jax.jit(f32_model.prefill)(mgr.params, batch)

    def rel(a, b):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errs = {"prefill_vs_f32": rel(one_shot, f32),
            "served_vs_f32": rel(served, f32),
            "served_vs_prefill": rel(served, one_shot)}
    floor = errs["prefill_vs_f32"]
    print(f"(d) logits: prefix {prefix}, {stats.tokens_reused} tokens from "
          f"{len(plan.models_used)} stored segments, {stats.tokens_computed} "
          f"computed, vocab {np.shape(f32)[-1]}, max|f32| "
          f"{np.abs(np.asarray(f32)).max():.3e}; errors as ‖Δ‖/‖ref‖")
    _check(f"LM.prefill ({mgr.model.cfg.compute_dtype}) vs f32 (the floor)",
           floor, BF16_FLOOR_MAX)
    _check("served vs f32", errs["served_vs_f32"],
           LOGIT_VS_F32 * floor + F32_NOISE)
    _check("served vs LM.prefill", errs["served_vs_prefill"],
           LOGIT_VS_PREFILL * floor + F32_NOISE)
    return errs


def phase_analytics(*, rows: int = 5_000_000, features: int = 10,
                    chunk: int = 8192, classes: int = 4,
                    seed: int = 0) -> dict:
    """(e) The paper's fits, ``backend="pallas"`` vs ``backend="numpy"``."""
    from repro.core import linreg, logreg, naive_bayes

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, features), np.float32)
    w_true = rng.standard_normal(features)
    y = (X @ w_true + 0.1 * rng.standard_normal(rows)).astype(np.float32)
    print(f"(e) paper kernels: {rows} rows x {features} features")
    errs = {}
    lp = linreg.fit(X, y, backend="pallas")
    ln = linreg.fit(X, y, backend="numpy")
    errs["linreg_A"] = _rel(lp.stats.A, ln.stats.A, ln.stats.A)
    errs["linreg_B"] = _rel(lp.stats.B, ln.stats.B, ln.stats.B)
    errs["linreg_w"] = _rel(lp.weights, ln.weights, ln.weights)
    for key in ("linreg_A", "linreg_B", "linreg_w"):
        _check(key, errs[key], STATS_TOL)

    labels = rng.integers(0, classes, rows)
    gp = naive_bayes.compute_gaussian_stats(X, labels, classes, backend="pallas")
    gn = naive_bayes.compute_gaussian_stats(X, labels, classes, backend="numpy")
    if not np.array_equal(gp.counts, gn.counts):
        raise AssertionError(f"naive Bayes counts differ: {gp.counts} vs "
                             f"{gn.counts}")
    errs["nb_S"] = _rel(gp.S, gn.S, gn.S)
    errs["nb_SS"] = _rel(gp.SS, gn.SS, gn.SS)
    print(f"  nb counts: exact over {classes} classes")
    for key in ("nb_S", "nb_SS"):
        _check(key, errs[key], STATS_TOL)

    Xc, yc = X[:chunk], (y[:chunk] > 0).astype(np.float32)
    sp = logreg.sgd_pass(Xc, yc, backend="pallas")
    sn = logreg.sgd_pass(Xc, yc, backend="numpy")
    errs["logreg_w"] = _rel(sp, sn, sn)
    _check(f"logreg one SGD epoch over {chunk} rows", errs["logreg_w"],
           SGD_TOL)
    return errs


class _CompileClock:
    """Sums XLA backend-compile seconds (persistent-cache hits add none)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def main() -> int:
    dev = require_tpu()
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    try:
        limit = phase_device(dev)["bytes_limit"]
        print(f"  compile cache: {cache}")

        tp = time.perf_counter()
        phase_kernels()
        print(f"  phase (b): {time.perf_counter() - tp:.1f} s wall", flush=True)

        tp = time.perf_counter()
        mgr = phase_serve(SERVE_ARGV)
        print(f"  phase (c): {time.perf_counter() - tp:.1f} s wall, "
              f"{clock.seconds:.1f} s compiling so far", flush=True)

        tp = time.perf_counter()
        phase_logits(mgr)
        peak = dev.memory_stats()["peak_bytes_in_use"]
        print(f"  phase (d): {time.perf_counter() - tp:.1f} s wall")
        print(f"  peak_bytes_in_use {peak} of bytes_limit {limit} "
              f"({peak / limit:.1%})", flush=True)
        if not peak < limit:
            raise AssertionError(f"peak {peak} B reached the limit {limit} B")
        del mgr
        gc.collect()                     # the model leaves the device

        tp = time.perf_counter()
        phase_analytics()
        print(f"  phase (e): {time.perf_counter() - tp:.1f} s wall")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    print(f"total {time.perf_counter() - t0:.1f} s wall, of which "
          f"{clock.seconds:.1f} s in {clock.count} XLA compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
