"""Pure-jnp oracle for the chunked logistic-regression SGD kernel.

Mirrors :func:`repro.core.logreg.sgd_pass` (single epoch, minibatch
updates, ``lr/√t`` decay) in fp32 jnp — the kernel must reproduce this
sequence of updates exactly (same order, same math).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def logreg_sgd_ref(X, y, mask, *, lam: float, lr: float, batch: int):
    """One SGD epoch over a chunk.  Returns (d+1,) weights, bias last.

    ``mask`` (n,) marks real rows; padded rows contribute nothing.
    """
    X = X.astype(jnp.float32)
    y = y.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    n, d = X.shape
    assert n % batch == 0
    steps = n // batch

    def body(t, carry):
        w, b = carry
        xb = jax.lax.dynamic_slice_in_dim(X, t * batch, batch, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, t * batch, batch, 0)
        mb = jax.lax.dynamic_slice_in_dim(mask, t * batch, batch, 0)
        z = xb @ w + b
        g = (jax.nn.sigmoid(z) - yb) * mb
        denom = jnp.maximum(mb.sum(), 1.0)
        # a minibatch with no real row takes no step, as in the kernel
        step = jnp.where(mb.sum() > 0.0, lr / jnp.sqrt(t.astype(jnp.float32) + 1.0), 0.0)
        gw = xb.T @ g / denom + 2.0 * lam * w
        gb = g.sum() / denom
        return (w - step * gw, b - step * gb)

    w, b = jax.lax.fori_loop(0, steps, body, (jnp.zeros((d,), jnp.float32), jnp.float32(0)))
    return jnp.concatenate([w, b[None]])
