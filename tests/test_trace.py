"""Host spans and counters of ``repro.core.trace`` on the analytics path.

Off, a span keeps nothing and writes no profiler annotation, and
``ExecTimings`` is still filled.  On, spans nest with their parent and
query id, self times subtract the children, and the five layers the spans
mark (planner, data backend and store, kernels' wrappers, device reads,
engine) split each ``repro.query`` exactly; each scan a plan makes is one
kernel call (``repro.kernel.calls``) and one device read
(``repro.device_reads``).
"""
import math
import time

import jax
import numpy as np
import pytest

from repro.core import trace
from repro.core.cost import CostModel
from repro.core.descriptors import Range
from repro.core.engine import IncrementalAnalyticsEngine
from repro.data.synthetic import make_classification, make_regression
from repro.data.tabular import ArrayBackend
from repro.kernels.common import round_up, row_bucket

FAMILIES = ("linreg", "gaussian_nb", "logreg")
#: the layers a query's spans fall into, as the benchmark reads them
LAYERS = {
    "planner": ("repro.plan",),
    "fetch": ("repro.fetch", "repro.load"),
    "wrapper": ("repro.kernel.prep",),
    "sync": ("repro.kernel.sync",),
    "engine": ("repro.query", "repro.stats", "repro.merge", "repro.solve"),
}


class FakeAnnotation:
    opened: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.opened.append(self.name)

    def __exit__(self, *exc):
        return None


@pytest.fixture(autouse=True)
def clean_trace(monkeypatch):
    FakeAnnotation.opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    monkeypatch.setattr(trace, "_annotation", FakeAnnotation)
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _engine(family, n=6_000):
    if family == "linreg":
        X, y = make_regression(n, d=4, seed=0)
    else:
        X, y = make_classification(n, d=4, n_classes=2, seed=1)
    # scans priced high, so that every plan reads stored models
    eng = IncrementalAnalyticsEngine(ArrayBackend(X, y), materialize="never",
                                     cost_model=CostModel(flops_per_s=1e7))
    eng.warm(family, [Range(500, 2_500), Range(3_000, 4_000)])
    return eng


def _params(family, backend):
    return {"backend": backend, **({"chunk_size": 700} if family == "logreg" else {})}


QUERIES = [Range(500, 2_450), Range(300, 4_100), Range(2_600, 5_100)]


def test_off_keeps_nothing_and_fills_exec_timings():
    eng = _engine("linreg")
    q = eng.query("linreg", Range(500, 2_450))
    trace.count("repro.device_reads", 5)
    assert trace.records() == []
    assert trace.summary() == {"spans": {}, "counters": {}, "compiles": {}}
    assert FakeAnnotation.opened == []
    t = q.timings
    assert q.used_reuse and q.plan.base_points > 0
    assert min(t.optimizer_s, t.io_s, t.compute_s, t.merge_s) > 0
    assert t.total_s == pytest.approx(t.optimizer_s + t.io_s + t.compute_s + t.merge_s)
    with trace.span("repro.block") as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002 and trace.records() == []


def test_spans_nest_with_parent_query_and_self_time():
    trace.enable()
    with trace.span("repro.query", query=True):
        with trace.span("repro.plan"):
            time.sleep(0.002)
        with trace.span("repro.stats"):
            with trace.span("repro.kernel.sync"):
                time.sleep(0.002)
    with trace.span("repro.query", query=True):
        pass
    with trace.span("repro.other"):
        pass
    recs = trace.records()
    names = [r[0] for r in recs]
    assert names == ["repro.query", "repro.plan", "repro.stats",
                     "repro.kernel.sync", "repro.query", "repro.other"]
    parents = [r[3] for r in recs]
    assert parents == [None, 0, 0, 2, None, None]
    assert [r[4] for r in recs] == [1, 1, 1, 1, 2, None]
    assert FakeAnnotation.opened == names
    dur = [r[2] - r[1] for r in recs]
    for _, lo, hi, parent, _ in recs:
        if parent is not None:
            assert recs[parent][1] <= lo <= hi <= recs[parent][2]
    s = trace.summary()["spans"]
    assert s["repro.query"]["count"] == 2
    assert s["repro.query"]["total_s"] == pytest.approx((dur[0] + dur[4]) * 1e-9)
    assert s["repro.query"]["self_s"] == pytest.approx(
        (dur[0] - dur[1] - dur[2] + dur[4]) * 1e-9)
    assert s["repro.stats"]["self_s"] == pytest.approx((dur[2] - dur[3]) * 1e-9)
    assert s["repro.kernel.sync"]["self_s"] == pytest.approx(dur[3] * 1e-9)
    assert s["repro.plan"]["total_s"] >= 0.002


def test_disable_stops_records_and_reset_drops_them():
    trace.enable()
    with trace.span("repro.a"):
        trace.count("repro.n", 2)
    trace.disable()
    with trace.span("repro.b"):
        trace.count("repro.n")
    got = trace.summary()
    assert list(got["spans"]) == ["repro.a"] and got["counters"] == {"repro.n": 2}
    trace.reset()
    assert trace.records() == [] and trace.summary()["counters"] == {}


def test_compiles_counted_under_the_innermost_span():
    trace.enable()
    f = jax.jit(lambda x: x * 3 + 1)
    with trace.span("repro.query", query=True):
        with trace.span("repro.kernel.prep"):
            f(np.zeros((7, 13), np.float32)).block_until_ready()
        f(np.zeros((7, 13), np.float32)).block_until_ready()   # cached
    f(np.zeros((5, 11), np.float32)).block_until_ready()
    assert trace.summary()["compiles"] == {"repro.kernel.prep": 1,
                                           trace.NO_SPAN: 1}


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("family", FAMILIES)
def test_layers_split_each_query(family, backend):
    eng = _engine(family)
    trace.enable()
    got = [eng.query(family, r, **_params(family, backend)) for r in QUERIES]
    s = trace.summary()["spans"]
    assert s["repro.query"]["count"] == len(QUERIES)
    layer = {k: sum(s[n]["self_s"] for n in names if n in s)
             for k, names in LAYERS.items()}
    assert set(s) <= {n for names in LAYERS.values() for n in names}
    assert sum(layer.values()) == pytest.approx(s["repro.query"]["total_s"], rel=0.05)
    # spans without children: self time is the whole span
    for n in ("repro.plan", "repro.fetch", "repro.load", "repro.kernel.sync"):
        if n in s:
            assert s[n]["self_s"] == pytest.approx(s[n]["total_s"])
    assert ("repro.kernel.prep" in s) == (backend == "pallas")
    # ExecTimings is the sum of the same spans
    t = [q.timings for q in got]
    assert sum(x.optimizer_s for x in t) == pytest.approx(s["repro.plan"]["total_s"])
    assert sum(x.io_s for x in t) == pytest.approx(
        sum(s[n]["total_s"] for n in ("repro.fetch", "repro.load") if n in s))
    assert sum(x.compute_s for x in t) == pytest.approx(s["repro.stats"]["total_s"])
    assert sum(x.merge_s for x in t) == pytest.approx(
        s["repro.merge"]["total_s"] + s["repro.solve"]["total_s"])


@pytest.mark.parametrize("family", FAMILIES)
def test_device_reads_per_kernel_call(family):
    eng = _engine(family)
    params = _params(family, "pallas")
    trace.enable()
    got = [eng.query(family, r, **params) for r in QUERIES]
    scans = [s.rng.size for q in got for s in q.plan.steps if s.model_id is None]
    assert scans
    counters = trace.summary()["counters"]
    assert counters["repro.kernel.calls"] == len(scans)
    assert counters["repro.device_reads"] == len(scans)
    assert trace.summary()["spans"]["repro.kernel.sync"]["count"] == len(scans)
    if family == "logreg":
        l = params["chunk_size"]
        slots = sum(math.ceil(n / l) * round_up(l, 64) for n in scans)
    else:
        slots = sum(row_bucket(n, 512) for n in scans)
    assert counters["repro.kernel.rows_padded"] == slots - sum(scans)
