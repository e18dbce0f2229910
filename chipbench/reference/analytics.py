"""Plain references of the paper's three model families, numpy float64.

They follow the paper's definitions (arXiv:1509.05066 §3-4) and import
nothing of the program: ridge linear regression from the sufficient
statistics ``A = X^T X``, ``B = X^T y``; Gaussian naive Bayes from
per-class counts, sums and sums of squares; logistic regression as the
mixture of one SGD epoch per chunk of ``l`` rows (Mann et al. 2009),
minibatches of ``batch`` rows, step ``lr / sqrt(t)``, L2 penalty ``lam``.
"""
from __future__ import annotations

import numpy as np


def linreg(X, y, lam: float) -> dict:
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    A = X.T @ X
    B = X.T @ y
    w = np.linalg.solve(A + lam * np.eye(A.shape[0]), B)
    return {"A": A, "B": B, "w": w}


def gaussian_nb(X, y, n_classes: int) -> dict:
    X = np.asarray(X, np.float64)
    y = np.asarray(y)
    counts = np.bincount(y, minlength=n_classes).astype(np.float64)
    S = np.zeros((n_classes, X.shape[1]))
    SS = np.zeros((n_classes, X.shape[1]))
    np.add.at(S, y, X)
    np.add.at(SS, y, X * X)
    return {"counts": counts, "S": S, "SS": SS}


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def sgd_epoch(X, y, lam: float, lr: float, batch: int) -> np.ndarray:
    """One SGD epoch from zero weights; (d + 1,) with the bias last."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    d = X.shape[1]
    w = np.zeros(d)
    b = 0.0
    for t, s in enumerate(range(0, len(y), batch), start=1):
        xb, yb = X[s:s + batch], y[s:s + batch]
        g = _sigmoid(xb @ w + b) - yb
        step = lr / np.sqrt(t)
        gw = xb.T @ g / len(yb) + 2.0 * lam * w
        w = w - step * gw
        b = b - step * g.mean()
    return np.concatenate([w, [b]])


def logreg_mixture(X, y, pieces, *, base: int, chunk: int, lam: float,
                   lr: float, batch: int) -> dict:
    """Mixture weights over ``pieces`` [(lo, hi)] of rows (absolute, with
    ``X[0]`` at row ``base``), each cut into chunks of ``chunk`` rows from
    its own start."""
    tot = None
    p = 0
    for lo, hi in pieces:
        for s in range(lo, hi, chunk):
            e = min(s + chunk, hi)
            w = sgd_epoch(X[s - base:e - base], y[s - base:e - base], lam, lr,
                          batch)
            tot = w if tot is None else tot + w
            p += 1
    return {"w": tot / p}


def rel_err(got: dict, ref: dict) -> float:
    """Worst over the answer's arrays of max|got - ref| / max|ref|."""
    worst = 0.0
    for k, r in ref.items():
        g = np.asarray(got[k], np.float64)
        r = np.asarray(r, np.float64)
        if g.shape != r.shape:
            return float("inf")
        worst = max(worst, float(np.abs(g - r).max() /
                                 max(np.abs(r).max(), 1e-300)))
    return worst
