"""Run one cell with the timed path broken underneath, for the tests.

    python faulty_run.py <fault> <run.py arguments...>

``answer``: every fitted statistic leaves out the last row it was given.
``half``: every fitted statistic is taken over half its rows, scaled up.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"     # before anything imports JAX

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))


def _wrap_stats(wrap) -> None:
    import dataclasses

    from repro.core import families

    for name, fam in list(families.FAMILIES.items()):
        families.FAMILIES[name] = dataclasses.replace(
            fam, compute_stats=wrap(fam.compute_stats))


def break_answers() -> None:
    def wrap(orig):
        def short(X, y, params):
            return orig(X[:-1], y[:-1], params) if len(X) > 1 else orig(X, y, params)
        return short
    _wrap_stats(wrap)


def break_half() -> None:
    def wrap(orig):
        def half(X, y, params):
            if len(X) < 2:
                return orig(X, y, params)
            st = orig(X[::2], y[::2], params)
            return st + st
        return half
    _wrap_stats(wrap)


if __name__ == "__main__":
    fault = sys.argv[1]
    {"answer": break_answers, "half": break_half}[fault]()
    import run

    sys.exit(run.main(sys.argv[2:]))
