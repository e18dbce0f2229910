"""One analyst, closed loop: ``IncrementalAnalyticsEngine.query`` calls back
to back, each a model fit over a row range, families in turn.

Range ends lie at any row.  Where the stored models lie and which ranges
the analyst asks for is the traffic file's layout, drawn from its own
``layout_seed``: every seed gets the same ranges, in another order, and
the seed makes the tables' contents.  The kernels' wrappers build programs
for each new gap length a plan scans, so the same ranges for every seed
mean the same programs: a checkout's first run compiles them, and every
later run finds them in the persistent cache.

Set-up makes the tables from the seed, fills each family's store to the
cell's coverage with materialized models (fitted by the program's host
path, as a store filled by earlier sessions would hold them), and runs the
seed's query list once, which builds every program the window will use.
The window runs the same list again in the same order, cycling where it
reaches the end; every fit returned in it counts.  Once the window has
closed, a sample of its answers, drawn from the seed, is compared with the
plain float64 reference over the same rows.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

import harness
import traffic
from reference import analytics as ref
from work import analytics as work

#: seeds (the first ones of a ``readings`` call) on which the control runs
CONTROL_SEEDS = 4
#: the statistics kernels and the constant that sets their MXU precision
KERNEL_MODULES = ("repro.kernels.linreg_stats.kernel",
                  "repro.kernels.nb_stats.kernel",
                  "repro.kernels.logreg_sgd.kernel")


def tables(cfg: dict, seed: int) -> dict:
    """The configuration's two tables, float32, from the seed: features from
    a random-covariance Gaussian; a planted linear target with noise, and
    class labels with per-class Gaussian centres."""
    n, d = int(cfg["rows"]), int(cfg["features"])
    rng = np.random.default_rng(traffic.seed32(seed, 3))
    dep = float(cfg["feature_dependency"])

    def mixing():
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        return ((1.0 - dep) * np.eye(d) + dep * q).astype(np.float32)

    m = mixing()
    w = rng.standard_normal(d).astype(np.float32)
    xr = rng.standard_normal((n, d), np.float32) @ m
    yr = xr @ w + np.float32(cfg["noise"]) * rng.standard_normal(n, np.float32)
    m = mixing()
    c = int(cfg["classes"])
    centers = (rng.standard_normal((c, d)) * cfg["class_separation"]).astype(np.float32)
    yc = rng.integers(0, c, n)
    xc = (centers[yc] + rng.standard_normal((n, d), np.float32)) @ m
    return {"regression": (xr, yr), "classification": (xc, yc)}


def model_ranges(n: int, coverage: float, size: dict, rng) -> list:
    """Materialized-model ranges at uniform positions until their union
    covers ``coverage`` of the rows."""
    from repro.core.descriptors import Range

    sizes = rng.permutation(traffic.sizes(size, 4096))
    covered = np.zeros(n, bool)
    out = []
    for s in sizes:
        if covered.mean() >= coverage:
            break
        lo = int(rng.integers(0, n - s + 1))
        covered[lo:lo + s] = True
        out.append(Range(lo, lo + int(s)))
    return out


def layout(n: int, tr: dict, per_family: int) -> tuple[dict, dict]:
    """The stored models' ranges and the query ranges of each family, the
    same for every seed: sizes at fixed quantiles, positions uniform."""
    from repro.core.descriptors import Range

    rng = np.random.default_rng(traffic.seed32(int(tr["layout_seed"]), 4))
    models, queries = {}, {}
    for fam in tr["families"]:
        models[fam] = model_ranges(n, float(tr["coverage"]), tr["model_size"], rng)
        sizes = rng.permutation(traffic.sizes(tr["query_size"], per_family))
        queries[fam] = [Range(lo, lo + int(s)) for s in sizes
                        for lo in [int(rng.integers(0, n - s + 1))]]
    return models, queries


def set_precision(name: str) -> None:
    """Set the statistics kernels' MXU precision (``highest``, as the
    configuration states, or ``default``: one bfloat16-rounded pass, the
    control) and drop every traced program, so the next call retraces."""
    import importlib

    import jax

    prec = {"highest": jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT}[name]
    for mod in KERNEL_MODULES:
        importlib.import_module(mod)._F32 = prec
    jax.clear_caches()


class Analyst:
    def __init__(self, seed: int, cell, tr, cfg) -> None:
        from repro.core.engine import IncrementalAnalyticsEngine
        from repro.data.tabular import ArrayBackend

        self.cfg, self.tr = cfg, tr
        self.data = tables(cfg, seed)
        self.table_of = tr["table_of"]
        self.params = {"linreg": {"lam": cfg["lam"]},
                       "gaussian_nb": {"n_classes": cfg["classes"]},
                       "logreg": {"lam": cfg["lam"], "lr": cfg["sgd_lr"],
                                  "chunk_size": cfg["logreg_chunk"]}}
        n = int(cfg["rows"])
        models, pool = layout(n, tr, int(cell["queries_per_family"]))
        self.engines = {}
        for fam in tr["families"]:
            X, y = self.data[self.table_of[fam]]
            eng = IncrementalAnalyticsEngine(
                ArrayBackend(X, y, n_classes=cfg["classes"]),
                materialize=tr["materialize"])
            eng.warm(fam, models[fam], backend=tr["model_backend"],
                     **self.params[fam])
            self.engines[fam] = eng
        # the seed's order: each family's ranges permuted, families in turn
        order = np.random.default_rng(traffic.seed32(seed, 6))
        perm = {fam: [pool[fam][j] for j in order.permutation(len(pool[fam]))]
                for fam in tr["families"]}
        self.queries = [(fam, perm[fam][i]) for i in range(len(pool[tr["families"][0]]))
                        for fam in tr["families"]]

    def query(self, i: int):
        fam, rng = self.queries[i % len(self.queries)]
        with harness.span("cb.query"):
            return self.engines[fam].query(fam, rng, backend=self.tr["backend"],
                                           **self.params[fam])

    # -- the reference ------------------------------------------------------
    def reference(self, fam: str, rng, plan) -> dict:
        """The plain float64 answer over the query's rows."""
        X, y = self.data[self.table_of[fam]]
        c = self.cfg
        if fam == "linreg":
            return ref.linreg(X[rng.lo:rng.hi], y[rng.lo:rng.hi], c["lam"])
        if fam == "gaussian_nb":
            return ref.gaussian_nb(X[rng.lo:rng.hi], y[rng.lo:rng.hi], c["classes"])
        pieces = sorted((s.rng.lo, s.rng.hi) for s in plan.steps)
        edges = [rng.lo] + [x for p in pieces for x in p] + [rng.hi]
        tiled = all(s.sign > 0 for s in plan.steps) and \
            all(a == b for a, b in zip(edges[0::2], edges[1::2]))
        if not tiled:
            return {"w": np.full(X.shape[1] + 1, np.inf)}
        return ref.logreg_mixture(X, y, pieces, base=0,
                                  chunk=int(c["logreg_chunk"]), lam=c["lam"],
                                  lr=c["sgd_lr"], batch=int(c["sgd_batch"]))

    @staticmethod
    def answer(fam: str, res) -> dict:
        st = res.stats
        if fam == "linreg":
            return {"A": st.A, "B": st.B, "w": res.model.weights}
        if fam == "gaussian_nb":
            return {"counts": st.counts, "S": st.S, "SS": st.SS}
        return {"w": res.model.weights}


def run(run: harness.Run, trace_dir) -> None:
    cell, tr, cfg = harness.cell_spec(run)
    compiles: harness.CompileCounter = run.notes["compiles"]
    an = Analyst(run.seed, cell, tr, cfg)
    k = len(an.queries)
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    for i in range(k):
        an.query(i)
        if (i + 1) % 60 == 0 or i + 1 == k:
            c = compiles.snapshot()
            print(f"set-up: {i + 1}/{k} queries, {c[0] - c0[0]} programs "
                  f"({c[1] - c0[1]} compiled, {compiles.compile_s:.1f} s), "
                  f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    c1 = compiles.snapshot()
    run.counters["warm_queries"] = k
    run.counters["warm_programs"] = c1[0] - c0[0]
    gc.collect()
    gc.freeze()             # no collection pass over set-up's objects in the window
    window = harness.Window(run.seconds, trace_dir)
    run.setup_s = time.perf_counter() - run.notes["t_start"]
    done = []
    i = 0
    w0 = window.open()
    while True:
        res = an.query(i)
        t = time.perf_counter()
        if t >= window.end:
            break
        done.append((i, res))
        i += 1
    w1 = window.close()
    c2 = compiles.snapshot()
    run.window_s = w1 - w0
    run.memory_peak_bytes = (run.device.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    run.attempted = len(done) + 1
    run.failed = 0
    run.end_to_end = {"fits_per_s": (len(done) / run.seconds, "fits/s"),
                      "setup_s": (run.setup_s, "s")}
    queried = scanned = 0
    ops = byt = 0.0
    d = int(cfg["features"])
    for i, res in done:
        fam, rng = an.queries[i % k]
        queried += rng.size
        for s in res.plan.steps:
            if s.model_id is None:
                scanned += s.rng.size
                o, b = work.KERNELS[fam](d, s.rng.size)
                ops += o
                byt += b
    run.counters.update(rows_queried=queried, rows_scanned=scanned,
                        window_programs=c2[0] - c1[0],
                        window_compiles=c2[1] - c1[1])
    run.work = {"analytics_kernels": (ops, byt)}
    print(f"window: {len(done)} fits; {run.counters}", file=sys.stderr)
    run.checks = check(an, dict(done), run.seed, cell["check"])


def _sample(an, answered: dict, seed: int, chk) -> list:
    """Distinct answered queries of each family, drawn from the seed."""
    rng = np.random.default_rng(traffic.seed32(seed, 5))
    seen = {}
    for i, res in answered.items():
        seen.setdefault(i % len(an.queries), res)
    per = int(chk["per_family"])
    out = []
    for fam in an.tr["families"]:
        idx = sorted(j for j in seen if an.queries[j][0] == fam)
        for j in rng.permutation(len(idx))[:per]:
            out.append((idx[j], seen[idx[j]]))
    return out


def check(an, answered: dict, seed: int, chk) -> list:
    """Per family, the worst over the sample of max|got - ref| / max|ref|
    over the answer's arrays, beside its limit."""
    worst = {fam: 0.0 for fam in an.tr["families"]}
    got = {fam: 0 for fam in an.tr["families"]}
    for j, res in _sample(an, answered, seed, chk):
        fam, rng = an.queries[j]
        want = an.reference(fam, rng, res.plan)
        worst[fam] = max(worst[fam], ref.rel_err(an.answer(fam, res), want))
        got[fam] += 1
    lim = chk["limits"]
    return [harness.Check(f"{fam}_rel_err",
                          worst[fam] if got[fam] else float("inf"),
                          float(lim[fam])) for fam in an.tr["families"]]


def tool(run: harness.Run, mode: str, count: int = 1) -> int:
    """``readings``: the numbers ``correct`` compares, without a window, on
    ``count`` seeds from ``--seed`` on: the program as the configuration
    states it (kernels at ``highest``) and the control (the program's own
    ``default`` path, one bfloat16-rounded MXU pass) on the first
    ``CONTROL_SEEDS`` of them.  Only the sampled queries run; one JSON line
    per seed and precision."""
    import json

    if mode != "readings":
        raise harness.BenchError(f"no tool mode {mode!r} in analytics_closed")
    cell, tr, cfg = harness.cell_spec(run)
    for s in range(run.seed, run.seed + count):
        an = Analyst(s, cell, tr, cfg)
        # the window's sample when every query was answered
        picks = [j for j, _ in _sample(an, dict.fromkeys(range(len(an.queries))),
                                       s, cell["check"])]
        for prec in ("highest", "default")[:2 if s - run.seed < CONTROL_SEEDS else 1]:
            set_precision(prec)
            t0 = time.perf_counter()
            answered = {j: an.query(j) for j in picks}
            got = check(an, answered, s, cell["check"])
            print(json.dumps({"seed": s, "precision": prec,
                              "seconds": time.perf_counter() - t0,
                              **{c.name: c.value for c in got}}), flush=True)
    set_precision("highest")
    return 0
