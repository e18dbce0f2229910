"""Public wrapper for the fused linreg-stats kernel: one host-packed
transfer and one jitted dispatch per scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels.common import pad_axis, round_up, row_bucket, use_interpret

from .kernel import zt_z


@functools.partial(jax.jit, static_argnames=("w", "block_n"))
def _gram(F: jnp.ndarray, *, w: int, block_n: int) -> jnp.ndarray:
    """``ZᵀZ`` of a row-bucketed ``Z = [X | y]`` sent flat: rows of ``w``
    taken here and their lanes padded to 128, the ``(w, w)`` block returned."""
    Z = F.reshape(-1, w)
    G = zt_z(pad_axis(Z, 1, round_up(w, 128)), block_n=block_n,
             interpret=use_interpret())
    return G[:w, :w]


def linreg_gram(X, y, *, block_n: int = 512) -> jax.Array:
    """``G = ZᵀZ`` for ``Z = [X | y]``, ``(d+1, d+1)`` float32 on the device:
    ``A = G[:d, :d]``, ``B = G[:d, d]``, ``yᵀy = G[d, d]``.

    ``Z`` is packed on the host at the scan's row bucket (zero rows are
    algebra-neutral, so the result is exact), sent as one flat buffer and
    reduced by one jitted program per (bucket, d).  Flat, because the
    device tiles a ``(rows, d+1)`` array's rows to 128 lanes, and the
    transfer then costs more than the on-device reshape.
    """
    with trace.span("repro.kernel.prep"):
        n, d = X.shape
        nb = row_bucket(n, block_n)
        Z = np.zeros((nb, d + 1), np.float32)
        Z[:n, :d] = X
        Z[:n, d] = y
        trace.count("repro.kernel.calls")
        trace.count("repro.kernel.rows_padded", nb - n)
        return _gram(jax.device_put(Z.reshape(-1)), w=d + 1, block_n=block_n)


def linreg_stats(X, y, *, block_n: int = 512, with_yty: bool = False):
    """Fused ``A = XᵀX``, ``B = Xᵀy`` (optionally ``yᵀy``) in one pass."""
    G = linreg_gram(X, y, block_n=block_n)
    d = G.shape[0] - 1
    A, B = G[:d, :d], G[:d, d]
    return (A, B, G[d, d]) if with_yty else (A, B)
