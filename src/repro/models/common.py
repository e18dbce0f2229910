"""Shared model machinery: parameter specs, norms, RoPE, activations.

Parameters are built from a **spec tree** (nested dicts with ``ParamSpec``
leaves).  The same tree serves three consumers without ever allocating:

  * ``init(key)``        — materializes arrays (jit-able, per-leaf fold_in)
  * ``shape_structs()``  — ShapeDtypeStructs (+sharding) for the dry-run
  * ``axes_tree()``      — logical-axis names consumed by distributed.sharding
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# cache-leaf taxonomy — the single source of truth for what each entry of a
# serving cache tree *is*.  The model creates these entries
# (LM._prefill_cache) and the serve layer slices/concats/stores them
# (repro.serve.kv_cache re-exports these under its own names).
# ---------------------------------------------------------------------------

#: entries whose trailing-from-batch axis is the document/sequence axis
CACHE_SEQ_KEYS = ("k", "v", "c_kv", "k_rope")
#: entries holding running state (SSD conv/ssm; kept only at segment end)
CACHE_STATE_KEYS = ("conv", "ssm")
#: entries constant across the document (cross-attention context K/V)
CACHE_CONST_KEYS = ("ck", "cv")


def cache_leaf_key(path) -> Optional[str]:
    """Innermost dict key of a cache-tree leaf path ("k", "ssm", …)."""
    for p in reversed(path):
        if hasattr(p, "key"):
            return p.key
    return None


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]   # logical axis per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones | small_normal
    scale: float = 1.0
    dtype: Any = None                 # filled by the model's param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_map(fn: Callable[[ParamSpec], Any], tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def _init_leaf(spec: ParamSpec, key, dtype) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    std = 0.02 * spec.scale if spec.init == "normal" else 0.006 * spec.scale
    # drawn in the param dtype: an f32 draw of a bf16 leaf would hold a
    # transient twice the leaf's size (2.9 GB for one stacked d_ff matrix
    # of a published-width model) on a chip that is already mostly weights
    return jax.random.normal(key, spec.shape, dtype) * jnp.asarray(std, dtype)


def init_params(specs, key, dtype):
    """Materialize the spec tree; per-leaf keys derived from the tree path."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def shape_structs(specs, dtype, sharding_fn=None):
    """ShapeDtypeStruct tree; ``sharding_fn(axes) -> Sharding`` optional."""

    def mk(s: ParamSpec):
        sh = sharding_fn(s.axes) if sharding_fn is not None else None
        return jax.ShapeDtypeStruct(s.shape, dtype, sharding=sh)

    return spec_map(mk, specs)


def axes_tree(specs):
    return spec_map(lambda s: s.axes, specs)


def param_bytes(specs, dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    return sum(int(np.prod(s.shape)) * itemsize for s in jax.tree.leaves(specs, is_leaf=is_spec))


def param_count(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs, is_leaf=is_spec))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def activation_fn(name: str):
    if name == "gelu":
        return jax.nn.gelu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "silu":
        return jax.nn.silu
    raise KeyError(name)  # swiglu handled structurally (gate ⊙ up)


def rope_angles(positions, head_dim: int, theta: float):
    """(…pos…) → cos/sin of shape (…pos…, head_dim/2), fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D/2) broadcast over heads."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def dense(x, w):
    """(…, d) @ (d, e) → (…, e)."""
    return jnp.einsum("...d,de->...e", x, w)


def proj_heads(x, w):
    """(…, d) @ (d, H, k) → (…, H, k) — per-head input projection."""
    return jnp.einsum("...d,dhk->...hk", x, w)


def proj_out(x, w):
    """(…, H, k) @ (H, k, d) → (…, d) — attention output projection."""
    return jnp.einsum("...hk,hkd->...d", x, w)
