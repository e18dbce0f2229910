"""Tests of the benchmark harness, on the CPU at tiny sizes.

Each cell is rehearsed end to end (``run.py --rehearse``) and its last
line checked against the result contract; the trace reduction runs on a
recorded chip trace; the peaks table, the work counts and the analytics
reference are checked against hand numbers and the program; the traffic
gives every seed the same ranges; the control switches the kernels'
precision; a cell, a configuration, a traffic mix, a driver and a metric
are added as new files alone; and ``correct`` comes out false when the
timed path is broken underneath.

Run:  PYTHONPATH=src python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

SEED = 2_718_281_829          # above 2**31: the driver's seeds are large


def _env(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["TMPDIR"] = str(tmp_path)
    return env


def _run(tmp_path, args, *, script=None, bench=BENCH, timeout=900):
    cmd = [sys.executable, str(script or bench / "run.py"), *args,
           "--rehearse", "--cache-dir", str(tmp_path / "jax_cache")]
    p = subprocess.run(cmd, cwd=str(bench.parent), env=_env(tmp_path),
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def _check_line(line, cell, trace):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "checks"
    spec = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    want = spec["per_layer"] if trace else spec["end_to_end"]
    assert set(line["metrics"]) <= set(want)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


CELLS = ["paper-5m-mix-cov80"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(tmp_path, cell):
    line, _ = _run(tmp_path, ["--workload", cell, "--seed", str(SEED),
                              "--seconds", "4", "--trace", "0"])
    _check_line(line, cell, trace=False)
    assert line["correct"] is True
    spec = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    assert set(line["metrics"]) == set(spec["end_to_end"])
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_rehearsal(tmp_path):
    line, _ = _run(tmp_path, ["--workload", "paper-5m-mix-cov80", "--seed",
                              str(SEED + 1), "--seconds", "4", "--trace", "1"])
    _check_line(line, "paper-5m-mix-cov80", trace=True)
    # the CPU has no device plane: device metrics are left out, never 0
    assert "device_idle_share.analytics" not in line["metrics"]
    assert "analytics_kernels_roofline" not in line["metrics"]
    assert 0 < line["metrics"]["reuse_share.analytics"]["value"] < 100
    assert line["metrics"]["compiles_per_query.analytics"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer", "half"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    line, err = _run(tmp_path, [fault, "--workload", "paper-5m-mix-cov80",
                                "--seed", str(SEED + 2), "--seconds", "4",
                                "--trace", "0"],
                     script=HERE / "faulty_run.py")
    assert line["correct"] is False, err[-2000:]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_readings_of_program_and_control(tmp_path):
    """The tool mode reads the program and its control on each seed.  On
    the CPU both multiply in float32, so only the chip tells them apart."""
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "paper-5m-mix-cov80", "--seed", str(SEED + 4),
                        "--seconds", "1", "--mode", "readings", "--count", "2",
                        "--rehearse", "--cache-dir", str(tmp_path / "c")],
                       cwd=str(ROOT), env=_env(tmp_path), capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = [json.loads(x) for x in p.stdout.strip().splitlines()
           if x.startswith("{")]
    assert [(g["seed"], g["precision"]) for g in got] == [
        (SEED + 4, "highest"), (SEED + 4, "default"),
        (SEED + 5, "highest"), (SEED + 5, "default")]
    spec = json.loads((BENCH / "workloads" / "paper-5m-mix-cov80.json").read_text())
    for g in got:
        for fam, limit in spec["rehearse"]["check"]["limits"].items():
            assert 0 <= g[f"{fam}_rel_err"] <= limit


def test_control_switches_the_kernels_precision():
    """The control's switch reaches the dot of each statistics kernel."""
    import jax
    import jax.numpy as jnp
    from drivers import analytics_closed as ac
    from repro.kernels.linreg_stats.kernel import zt_z

    def precision_of():
        jaxpr = jax.make_jaxpr(lambda z: zt_z(z, block_n=8, interpret=True))(
            jnp.zeros((8, 128), jnp.float32))
        return "HIGHEST" in str(jaxpr)

    try:
        ac.set_precision("default")
        assert not precision_of()
        for mod in ac.KERNEL_MODULES:
            assert __import__(mod, fromlist=["_F32"])._F32 == jax.lax.Precision.DEFAULT
    finally:
        ac.set_precision("highest")
    assert precision_of()


def test_layout_is_the_same_for_every_seed():
    """Every seed asks for the same ranges, in another order: the same
    programs, the same work."""
    from drivers import analytics_closed as ac
    spec = json.loads((BENCH / "workloads" / "paper-5m-mix-cov80.json").read_text())
    tr = json.loads((BENCH / "traffic" / "paper-mix-cov80.json").read_text())
    tr.update(spec["rehearse"]["traffic"])
    n = spec["rehearse"]["config"]["rows"]
    m1, q1 = ac.layout(n, tr, 5)
    m2, q2 = ac.layout(n, tr, 5)
    assert m1 == m2 and q1 == q2
    for fam, qs in q1.items():
        assert all(0 <= r.lo < r.hi <= n for r in qs)
        # ends at any row, not on a grid
        assert len({r.hi % 256 for r in qs}) > 1
    covered = np.zeros(n, bool)
    for r in m1["linreg"]:
        covered[r.lo:r.hi] = True
    assert covered.mean() >= tr["coverage"]


# -- the trace reduction ------------------------------------------------------
NAMES = {"steps": {"decode_step": ["jit_decode_step"],
                   "extend": ["jit_prefill_extend"]},
         "kernels": {"decode_attention": ["decode_attention_streams"],
                     "extend_attention": ["extend_attention_streams"]}}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A chip trace (TPU v5 lite) of three batched decode steps of
    deepseek-67b-4L (32 rows, 3840 KV positions) and one prefix build."""
    raw = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    raw.write_bytes(gzip.decompress(
        (HERE / "fixtures" / "decode_extend.xplane.pb.gz").read_bytes()))
    from trace_reduce import reduce_file
    return reduce_file(raw, NAMES)


def test_trace_reduce_counts_steps_and_kernels(recorded):
    r = recorded
    assert r.n_devices == 1
    assert r.steps["decode_step"][1] == 3               # three decode calls
    assert r.kernels["decode_attention"][1] == 12       # 3 calls x 4 layers
    assert r.steps["extend"][1] == 1
    # kernel time lies inside its steps' time, busy time inside the window
    assert 0 < r.kernels["decode_attention"][0] < r.steps["decode_step"][0]
    assert 0 < r.busy_s <= r.window_s
    assert 0 <= r.idle_share < 1


def test_trace_reduce_breakdown(recorded):
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert "decode_attention_streams" in [n for n, _ in b["device_ops"]]
    assert {n for n, _ in b["idle_gaps"]} <= {"cb.step", "cb.submit", "no span"}


def test_op_names():
    from trace_reduce import op_name
    assert op_name("%decode_attention_streams.4 = bf16[256,8,128]{2,1,0} "
                   "custom-call(%a)") == "decode_attention_streams"
    assert op_name("jit_decode_step(8968772137157370588)") == "jit_decode_step"
    assert op_name("%fusion.78 = bf16[32,102400]") == "fusion"


def test_reduce_planes_union_and_gaps():
    from trace_reduce import reduce_planes

    class E:
        def __init__(self, name, a, b):
            self.name, self.start_ns, self.end_ns = name, a, b
            self.duration_ns = b - a

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    ms = 1_000_000
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_a(1)", 0, 10 * ms), E("jit_a(1)", 5 * ms, 20 * ms),
                          E("jit_b(2)", 60 * ms, 70 * ms)]),
        L("XLA Ops", [E("%k.3 = f32[] custom-call()", 1 * ms, 4 * ms)])])
    host = P("/host:CPU", [L("python3", [E("cb.window", 0, 100 * ms),
                                         E("cb.step", 20 * ms, 60 * ms)])])
    r = reduce_planes([dev, host], {"steps": {"a": ["jit_a"]},
                                    "kernels": {"k": ["k"]}})
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.03)              # [0, 20) and [60, 70)
    assert r.steps["a"] == [pytest.approx(0.025), 2]
    assert r.kernels["k"] == [pytest.approx(0.003), 1]
    assert r.idle_gaps[0] == ["cb.step", pytest.approx(0.04)]
    assert r.idle_gaps[1] == ["no span", pytest.approx(0.03)]


# -- peaks and work counts ------------------------------------------------------
def test_peaks_lookup_and_unknown_device():
    import harness
    pk = harness.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        harness.peaks("TPU v9 imaginary")


def test_analytics_work_by_hand():
    from work import analytics
    assert analytics.row_bytes(10) == 44
    assert analytics.linreg_stats(10, 1000) == (2 * 121 * 1000, 44_000)
    assert analytics.logreg_sgd(10, 3)[1] == 132


# -- the analytics reference against the program, small -----------------------
def test_analytics_reference_matches_core():
    from repro.core import linreg, logreg, naive_bayes
    from reference import analytics as ref

    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 5))
    y = X @ rng.standard_normal(5) + 0.1 * rng.standard_normal(3000)
    got = linreg.fit(X, y, lam=1e-3)
    want = ref.linreg(X, y, 1e-3)
    assert ref.rel_err({"A": got.stats.A, "B": got.stats.B, "w": got.weights},
                       want) < 1e-12
    lab = rng.integers(0, 3, 3000)
    gs = naive_bayes.compute_gaussian_stats(X, lab, 3)
    assert ref.rel_err({"counts": gs.counts, "S": gs.S, "SS": gs.SS},
                       ref.gaussian_nb(X, lab, 3)) < 1e-12
    yb = (y > 0).astype(np.float64)
    w = logreg.sgd_pass(X[:1000], yb[:1000], lam=1e-3, lr=0.5, batch=64)
    assert np.abs(w - ref.sgd_epoch(X[:1000], yb[:1000], 1e-3, 0.5, 64)).max() < 1e-12


# -- a new cell is new files alone --------------------------------------------
def test_new_cell_from_new_files_only(tmp_path):
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "tiny-echo.json").write_text(json.dumps(
        {"name": "tiny-echo", "source": "a fixture", "reduced": {}}))
    (bench / "traffic" / "echo-mix.json").write_text(json.dumps(
        {"driver": "echo_loop", "value": 7.0}))
    (bench / "workloads" / "tiny-echo.echo.json").write_text(json.dumps(
        {"config": "tiny-echo", "traffic": "echo-mix", "chips": 1,
         "end_to_end": ["echo_per_s", "setup_s"], "per_layer": ["echo_share"]}))
    (bench / "drivers" / "echo_loop.py").write_text(
        "import harness\n"
        "def run(run, trace_dir):\n"
        "    v = run.cell.traffic['value']\n"
        "    run.setup_s = 0.5\n"
        "    run.attempted = 3\n"
        "    run.counters = {'echo': v}\n"
        "    run.end_to_end = {'echo_per_s': (v, 'echo/s'), 'setup_s': (0.5, 's')}\n"
        "    run.checks = [harness.Check('echo_err', 0.0, 1.0)]\n")
    (bench / "metrics" / "echo_share.py").write_text(
        "NAME, UNIT, LAYER, SOURCE, MOVES = ('echo_share', '%', 'echo', "
        "'program_counter', 'echo_per_s')\n"
        "def read(run):\n    return run.counters['echo'] * 10\n")
    line, _ = _run(tmp_path, ["--workload", "tiny-echo.echo", "--seed", str(SEED),
                              "--seconds", "1", "--trace", "0"], bench=bench)
    assert line["metrics"]["echo_per_s"] == {"value": 7.0, "unit": "echo/s"}
    assert line["correct"] is True
    after = {p: p.read_bytes() for p in before}
    assert after == before                  # no file the benchmark had changed


def test_no_result_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark, a run
    exits non-zero and prints no result."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = _env(tmp_path)
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "paper-5m-mix-cov80", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=str(tmp_path), env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_no_result_without_a_chip(tmp_path):
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "paper-5m-mix-cov80", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=str(ROOT), env=_env(tmp_path),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "not a TPU" in p.stderr
