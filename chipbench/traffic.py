"""The one traffic generator: every mix is a file of parameters it reads.

Each seed gets the same multiset of sizes, drawn at fixed quantiles of
the stated distribution, in another order.  So two seeds do the same
amount of work, and the spread between runs is the system's, not the
generator's.
"""
from __future__ import annotations

import math

import numpy as np


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit stream key from any whole-number seed and a salt, one
    salt per use (tables, layout, order, samples)."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9), so the generator needs numpy alone."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo = p < 0.02425
    hi = p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5])
               / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5])
                / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q
                / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1))
    return out


def sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` whole sizes at fixed quantiles of ``spec``, in ascending order.

    ``spec``: ``dist`` (``lognormal`` with ``median`` and ``sigma``,
    ``normal`` with ``mean`` and ``std``, or ``fixed`` with ``value``),
    clipped to ``[min, max]`` and rounded down.
    """
    p = quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(p))
    elif kind == "normal":
        x = spec["mean"] + spec["std"] * _norm_ppf(p)
    elif kind == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    x = np.clip(x, spec.get("min", -math.inf), spec.get("max", math.inf))
    return np.floor(x).astype(np.int64)
