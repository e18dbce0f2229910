"""Shared kernel utilities: padding, interpret-mode detection, routing."""
from __future__ import annotations

import math
import os

import jax
import numpy as np


def use_interpret() -> bool:
    """Pallas interpret mode everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def kernel_mode(name: str, *, off: str, off_aliases: tuple[str, ...] = (),
                fallback: str | None = None) -> str:
    """Shared env routing for the Pallas kernels: 'kernel' | ``off`` | ``fallback``.

    Reads ``REPRO_{name}_KERNEL``: ``1/on/true/kernel`` forces the Pallas
    kernel (interpret mode off-TPU — the parity harness, ~100× slower than
    XLA), ``0/off/false`` (or ``off``/any of ``off_aliases`` by name) forces
    the non-kernel path, anything else is ``auto``: kernel on TPU, and off
    elsewhere — except when ``fallback`` names an intermediate pure-JAX path
    (e.g. decode's blocked softmax), which then wins on CPU and is also
    selectable by name.

    The mode is read at jit *trace* time: set the env var before building
    an engine/builder.  Flipping it later in the same process does not
    re-route executables already cached for a shape.
    """
    env = os.environ.get(f"REPRO_{name}_KERNEL", "auto").strip().lower()
    if env in ("1", "on", "true", "kernel"):
        return "kernel"
    if env in ("0", "off", "false", off) or env in off_aliases:
        return off
    if fallback is not None and env == fallback:
        return fallback
    if jax.default_backend() == "tpu":
        return "kernel"
    return fallback if fallback is not None else off


def extend_kernel_mode() -> str:
    """How ``prefill_extend`` runs its suffix attention: 'kernel' | 'jax'.

    'kernel' routes through ``kernels/extend_attention`` (Pallas; interpret
    mode off-TPU), 'jax' uses the pure-JAX blocked-softmax path.  Default is
    kernel on TPU and blocked elsewhere; ``REPRO_EXTEND_KERNEL=1/0``
    overrides.  See ``kernel_mode`` for trace-time semantics.
    """
    return kernel_mode("EXTEND", off="jax", off_aliases=("blocked",))


def quant_kernel_mode() -> str:
    """How quantized segments dequantize on reuse: 'kernel' | 'ref'.

    'kernel' routes through ``kernels/quant_kv``'s fused Pallas dequant
    (interpret mode off-TPU), 'ref' the pure-jnp blocked reference —
    which on CPU is the fast path (XLA fuses the cast+scale), so the
    default mirrors ``extend_kernel_mode``: kernel on TPU, reference
    elsewhere.  ``REPRO_QUANT_KERNEL=1/0`` overrides.
    """
    return kernel_mode("QUANT", off="ref", off_aliases=("jax",))


def decode_kernel_mode() -> str:
    """How one-token decode attention runs: 'kernel' | 'blocked' | 'dense'.

    'kernel' routes through ``kernels/decode_attention``'s ragged
    flash-decode Pallas kernel (per-row early exit over KV blocks;
    interpret mode off-TPU), 'blocked' the pure-JAX online-softmax
    fallback (O(B·block) score peak, pack-level early exit), 'dense' the
    original full-T score materialization — bit-identical to the
    pre-kernel decode path.  ``REPRO_DECODE_KERNEL=1/0`` overrides
    (``blocked`` selects the fallback by name); default is kernel on TPU
    and blocked elsewhere.  Read at jit trace time — see ``kernel_mode``.
    """
    return kernel_mode("DECODE", off="dense", fallback="blocked")


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def bucket_len(x: int, bucket: int, *, floor: int = 1) -> int:
    """Pad-to-bucket length: smallest bucket multiple ≥ max(x, floor).

    Batched serving pads every sequence in a decode batch to a shared
    bucketed capacity so jitted kernels see a small, reusable set of shapes
    instead of one compilation per (batch, seq-len) pair.
    """
    return round_up(max(x, floor), bucket)


#: the smallest row bucket of a statistics scan; buckets grow by 2^(1/4)
ROW_BUCKET_BASE = 512


def row_bucket(n: int, block_n: int) -> int:
    """Rows a scan of ``n`` rows is padded to: the smallest
    ``512·2^(k/4)`` ≥ max(n, 512), rounded up to a multiple of ``block_n``.

    Quarter-octave buckets waste at most 19% of rows (2^(1/4) ≈ 1.19) and
    give each jitted statistics program a few dozen row counts to compile
    for, where padding to the exact length would build one per length.
    """
    k = 0
    while ROW_BUCKET_BASE * 2 ** (k / 4) < n:
        k += 1
    return round_up(math.ceil(ROW_BUCKET_BASE * 2 ** (k / 4)), block_n)


def pad_axis(x, axis: int, target: int, value=0.0):
    """Zero-pad ``x`` along ``axis`` up to length ``target``."""
    import jax.numpy as jnp

    cur = x.shape[axis]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=value)
