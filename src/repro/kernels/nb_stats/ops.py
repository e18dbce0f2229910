"""Public wrapper for the NB grouped-statistics kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.trace import span
from repro.kernels.common import pad_axis, round_up, use_interpret

from .kernel import grouped_stats


def nb_stats(X, y, n_classes: int, *, block_n: int = 512):
    """Per-class ``(counts, S, SS)`` from one fused pass over X."""
    with span("repro.kernel.prep"):
        X = jnp.asarray(X)
        y = jnp.asarray(y, jnp.int32)
        n, d = X.shape
        dp = round_up(d, 128)
        cp = round_up(max(n_classes, 8), 8)
        npad = round_up(max(n, block_n), block_n)
        Xp = pad_axis(pad_axis(X, 1, dp), 0, npad)
        yp = pad_axis(y[:, None], 0, npad, value=-1)  # padding rows: class −1
        G = grouped_stats(Xp, yp, n_classes_padded=cp, block_n=block_n,
                          interpret=use_interpret())
        counts = G[:n_classes, 0]
        S = G[:n_classes, 1 : 1 + d]
        SS = G[:n_classes, 1 + dp : 1 + dp + d]
    return counts, S, SS
