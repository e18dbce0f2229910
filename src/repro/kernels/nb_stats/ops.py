"""Public wrapper for the NB grouped-statistics kernel: one host-packed
transfer and one jitted dispatch per scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels.common import pad_axis, round_up, row_bucket, use_interpret

from .kernel import grouped_stats


@functools.partial(jax.jit, static_argnames=("w", "n_classes", "block_n"))
def _grouped(F: jnp.ndarray, *, w: int, n_classes: int, block_n: int) -> jnp.ndarray:
    """Per-class ``[count | S | SS]`` of a row-bucketed ``Z = [X | label]``
    sent flat."""
    Z = F.reshape(-1, w)
    d = w - 1
    dp = round_up(d, 128)
    G = grouped_stats(pad_axis(Z[:, :d], 1, dp), Z[:, d:].astype(jnp.int32),
                      n_classes_padded=round_up(max(n_classes, 8), 8),
                      block_n=block_n, interpret=use_interpret())
    return jnp.concatenate([G[:n_classes, : 1 + d],
                            G[:n_classes, 1 + dp : 1 + dp + d]], axis=1)


def nb_grouped(X, y, n_classes: int, *, block_n: int = 512) -> jax.Array:
    """Per-class ``[N_c | S_c | SS_c]``, ``(C, 1 + 2d)`` float32 on the device.

    ``Z = [X | label]`` is packed on the host at the scan's row bucket, the
    labels as float32 (exact for any class index below 2^24) and −1 on the
    padding rows, which match no class; sent as one flat buffer, as in
    ``linreg_gram``, and reduced by one jitted program per (bucket, d, C).
    """
    with trace.span("repro.kernel.prep"):
        n, d = X.shape
        nb = row_bucket(n, block_n)
        Z = np.zeros((nb, d + 1), np.float32)
        Z[:n, :d] = X
        Z[:n, d] = np.asarray(y, np.int32)
        Z[n:, d] = -1.0
        trace.count("repro.kernel.calls")
        trace.count("repro.kernel.rows_padded", nb - n)
        return _grouped(jax.device_put(Z.reshape(-1)), w=d + 1, n_classes=n_classes,
                        block_n=block_n)


def nb_stats(X, y, n_classes: int, *, block_n: int = 512):
    """Per-class ``(counts, S, SS)`` from one fused pass over X."""
    G = nb_grouped(X, y, n_classes, block_n=block_n)
    d = (G.shape[1] - 1) // 2
    return G[:, 0], G[:, 1 : 1 + d], G[:, 1 + d :]
