"""Public wrapper for the fused linreg-stats kernel (padding + dispatch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.trace import span
from repro.kernels.common import pad_axis, round_up, use_interpret

from .kernel import zt_z


@functools.partial(jax.jit, static_argnames=("d", "block_n"))
def _linreg_stats_padded(Z: jnp.ndarray, d: int, *, block_n: int) -> tuple:
    G = zt_z(Z, block_n=block_n, interpret=use_interpret())
    return G[:d, :d], G[:d, d], G[d, d]


def linreg_stats(X, y, *, block_n: int = 512, with_yty: bool = False):
    """Fused ``A = XᵀX``, ``B = Xᵀy`` (optionally ``yᵀy``) in one pass.

    Accepts arbitrary (n, d); zero-pads rows (zero rows are algebra-neutral)
    and features up to lane alignment.
    """
    with span("repro.kernel.prep"):
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        n, d = X.shape
        Z = jnp.concatenate([X, y[:, None].astype(X.dtype)], axis=1)
        dp = round_up(d + 1, 128)
        npad = round_up(max(n, block_n), block_n)
        Z = pad_axis(pad_axis(Z, 1, dp), 0, npad)
        A, B, yty = _linreg_stats_padded(Z, d=d, block_n=block_n)
    return (A, B, yty) if with_yty else (A, B)
