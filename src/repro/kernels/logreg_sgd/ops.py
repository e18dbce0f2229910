"""Public wrapper for the chunked SGD kernel (padding + single-chunk API)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.trace import span
from repro.kernels.common import pad_axis, round_up, use_interpret

from .kernel import sgd_chunks

_VMEM_FP32_BUDGET = 1_500_000  # chunk floats pinned in VMEM (~6 MB)


def logreg_sgd(X, y, *, lam: float = 1e-3, lr: float = 0.5, batch: int = 64):
    """One SGD epoch over one chunk → (d+1,) weights (bias last)."""
    with span("repro.kernel.prep"):
        w, b = _sgd_padded(X[None], y[None], lam=lam, lr=lr, batch=batch)
        return jnp.concatenate([w[0], b[0]])


def logreg_sgd_batched(X, y, *, lam: float = 1e-3, lr: float = 0.5, batch: int = 64):
    """(p, l, d), (p, l) → per-chunk weights (p, d) and bias (p, 1).

    Pads rows to a batch multiple (mask-neutral) and features to lane width.
    """
    with span("repro.kernel.prep"):
        return _sgd_padded(X, y, lam=lam, lr=lr, batch=batch)


def _sgd_padded(X, y, *, lam, lr, batch):
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    p, l, d = X.shape
    dp = round_up(d, 128)
    lp = round_up(l, batch)
    if lp * dp > _VMEM_FP32_BUDGET:
        raise ValueError(
            f"chunk {lp}x{dp} exceeds VMEM budget; shrink chunk_size or batch"
        )
    mask = jnp.ones((p, l), jnp.float32)
    Xp = pad_axis(pad_axis(X, 2, dp), 1, lp)
    yp = pad_axis(y, 1, lp)
    mp = pad_axis(mask, 1, lp)
    rows = (p, lp // batch, batch)              # one row per minibatch
    w, b = sgd_chunks(Xp, yp.reshape(rows), mp.reshape(rows), lam=lam, lr=lr,
                      batch=batch, interpret=use_interpret())
    return w[:, 0, :d], b[:, 0, :1]
