"""Public wrapper for the chunked SGD kernel: a scan's chunks packed on the
host, sent as one flat buffer and run by one jitted dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels.common import pad_axis, round_up, use_interpret

from .kernel import sgd_chunks

_VMEM_FP32_BUDGET = 1_500_000  # chunk floats pinned in VMEM (~6 MB)


@functools.partial(jax.jit, static_argnames=("shape", "lam", "lr", "batch"))
def _sgd(F: jnp.ndarray, *, shape: tuple, lam: float, lr: float, batch: int) -> jnp.ndarray:
    """Per-chunk ``[w | b]`` of packed chunks ``Z = [X | y | mask]`` sent flat."""
    Z = F.reshape(shape)
    p, lp, w = shape
    d = w - 2
    rows = (p, lp // batch, batch)              # one row per minibatch
    wt, b = sgd_chunks(pad_axis(Z[..., :d], 2, round_up(d, 128)),
                       Z[..., d].reshape(rows), Z[..., d + 1].reshape(rows),
                       lam=lam, lr=lr, batch=batch, interpret=use_interpret())
    return jnp.concatenate([wt[:, 0, :d], b[:, 0, :1]], axis=1)


def _slots(p: int, l: int, d: int, batch: int) -> np.ndarray:
    """Zeroed host buffer for ``p`` chunks of ``[X | y | mask]``, each in a
    slot of ``round_up(l, batch)`` rows."""
    lp, dp = round_up(l, batch), round_up(d, 128)
    if lp * dp > _VMEM_FP32_BUDGET:
        raise ValueError(
            f"chunk {lp}x{dp} exceeds VMEM budget; shrink chunk_size or batch"
        )
    return np.zeros((p, lp, d + 2), np.float32)


def _dispatch(Z: np.ndarray, n: int, *, lam, lr, batch) -> jax.Array:
    trace.count("repro.kernel.calls")
    trace.count("repro.kernel.rows_padded", Z.shape[0] * Z.shape[1] - n)
    return _sgd(jax.device_put(Z.reshape(-1)), shape=Z.shape, lam=lam, lr=lr,
                batch=batch)


def logreg_sgd_chunks(X, y, *, chunk: int, lam: float = 1e-3, lr: float = 0.5,
                      batch: int = 64) -> jax.Array:
    """One SGD epoch per ``chunk``-row chunk of a scan → ``(p, d+1)`` float32
    weights on the device, bias last, one row per chunk in order.

    Every chunk takes a slot of ``round_up(chunk, batch)`` rows; the rows
    past a short tail chunk's end are masked, and their minibatches take no
    step, so each chunk's weights are those of its own one-chunk call.
    """
    with trace.span("repro.kernel.prep"):
        n, d = X.shape
        p = max(-(-n // chunk), 1)
        Z = _slots(p, chunk, d, batch)
        for i, s in enumerate(range(0, n, chunk)):
            m = min(chunk, n - s)
            Z[i, :m, :d] = X[s : s + m]
            Z[i, :m, d] = y[s : s + m]
            Z[i, :m, d + 1] = 1.0
        return _dispatch(Z, n, lam=lam, lr=lr, batch=batch)


def logreg_sgd(X, y, *, lam: float = 1e-3, lr: float = 0.5, batch: int = 64):
    """One SGD epoch over one chunk → (d+1,) weights (bias last)."""
    return logreg_sgd_chunks(X, y, chunk=max(len(y), 1), lam=lam, lr=lr,
                             batch=batch)[0]


def logreg_sgd_batched(X, y, *, lam: float = 1e-3, lr: float = 0.5, batch: int = 64):
    """(p, l, d), (p, l) → per-chunk weights (p, d) and bias (p, 1).

    Pads rows to a batch multiple (mask-neutral) and features to lane width.
    """
    with trace.span("repro.kernel.prep"):
        p, l, d = X.shape
        Z = _slots(p, l, d, batch)
        Z[:, :l, :d] = X
        Z[:, :l, d] = y
        Z[:, :l, d + 1] = 1.0
        W = _dispatch(Z, p * l, lam=lam, lr=lr, batch=batch)
    return W[:, :d], W[:, d:]
