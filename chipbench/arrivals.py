"""Open-loop request schedules: arrival gaps, item popularity and answer
lengths at fixed quantiles, in an order drawn from the traffic's layout
seed, like ``traffic.sizes``' sizes.
"""
from __future__ import annotations

import numpy as np

import traffic


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps of a Poisson process of ``rate`` per second, at fixed
    quantiles of the exponential distribution."""
    return -np.log1p(-traffic.quantiles(n)) / float(rate)


def zipf_ranks(n_items: int, s: float, n: int) -> np.ndarray:
    """``n`` item ranks (0 most popular) at fixed quantiles of Zipf(``s``)
    over ``n_items`` items."""
    w = 1.0 / np.arange(1, n_items + 1) ** float(s)
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, traffic.quantiles(n)), n_items - 1)


def schedule(tr: dict, rate: float, seconds: float, salt: int) -> list:
    """Requests due in ``[0, seconds)`` at ``rate`` per second: a list of
    ``(due_s, item_rank, answer_len)`` in due order.

    The count is ``round(rate * seconds)``; gaps, ranks and answer lengths
    are each in an order drawn from the traffic's ``layout_seed``.  The
    schedule is one trace, replayed for every seed: runs of different
    seeds (weights, document tokens, the requests checked) offer the same
    requests at the same times, so their spread is the system's.
    """
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng(traffic.seed32(int(tr["layout_seed"]), salt))
    gaps = rng.permutation(exp_gaps(rate, n))
    ranks = rng.permutation(zipf_ranks(int(tr["docs"]), tr["zipf_s"], n))
    answers = rng.permutation(traffic.sizes(tr["answer_len"], n))
    # the multiset of gaps sums to about n / rate; scale it onto the window
    due = np.cumsum(gaps) - gaps[0]
    due = due * (seconds * (n - 1) / n) / max(due[-1], 1e-12) if n > 1 else due
    return [(float(t), int(r), int(a)) for t, r, a in zip(due, ranks, answers)]
