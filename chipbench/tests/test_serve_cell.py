"""Tests of the serving workload's benchmark files, on the CPU at tiny sizes.

The plain reference matches the program's prefill and its decode through
the cache on seeded random weights; the workload rehearses end to end,
plain and traced, and prints the contract line; the precision control and
two faults of the timed path (a served token altered, a prompt chunk's KV
zeroed) make ``correct`` come out false; the work counts match hand
numbers; the schedules give every seed the same work; the configuration
keeps the program's published widths; the pool outgrows its store.

Run:  PYTHONPATH=src python -m pytest chipbench/tests/test_serve_cell.py -q
"""
from __future__ import annotations

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from test_chipbench import SEED, _check_line, _env, _run  # noqa: E402

CELL = "ds67b-docqa-shared"


def _spec(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def _tiny_cfg(dtype: str = "bfloat16") -> dict:
    cfg = _spec("configs", "deepseek-67b-4L")
    cfg.update(cfg.pop("rehearse"))
    cfg["torch_dtype"] = dtype
    return cfg


def _driver():
    import harness
    return harness.load_module("drivers", "serve_open")


# -- the reference against the program ------------------------------------------
def test_reference_matches_prefill_and_decode():
    """Float32 program and reference on the same seeded weights: the last
    prompt position's logits from ``LM.prefill``, then three greedy
    decode steps through the cache, against the reference's teacher-forced
    forward pass over the prompt and the decoded tokens."""
    import jax
    import jax.numpy as jnp
    from reference import serve as ref
    from repro.models.lm import LM

    so = _driver()
    cfg = _tiny_cfg("float32")
    seed = SEED
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          so.make_params(seed, cfg))
    model = LM(so.arch(cfg))
    prompt = np.random.default_rng(0).integers(1, cfg["vocab_size"], 40,
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, caches = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
        caches = jax.tree.map(
            lambda x: jnp.pad(x, [(0, 0)] * 2 + [(0, 24)] + [(0, 0)] * (x.ndim - 3)),
            caches)
        got, toks = [np.asarray(logits[0])], []
        for i in range(3):
            tok = int(np.argmax(got[-1]))
            toks.append(tok)
            logits, caches = model.decode_step(
                params, caches, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray([len(prompt) + i], jnp.int32))
            got.append(np.asarray(logits[0]))
    seq = np.zeros((1, 64), np.int32)
    seq[0, :len(prompt) + 3] = np.concatenate([prompt, toks])
    want = ref.forward_logits(seed, cfg, seq, [list(range(len(prompt) - 1,
                                                          len(prompt) + 3))],
                              q_block=32)[0]
    for g, w in zip(got, want):
        # float32 on both sides, summed in another order: rounding only
        assert np.linalg.norm(g - w) / np.linalg.norm(w) < 1e-4


def test_program_weights_are_the_reference_draws():
    """Layer i of every stacked leaf the program gets is the reference's
    own draw of layer i."""
    import jax
    from reference import serve as ref

    so = _driver()
    cfg = _tiny_cfg()
    params = so.make_params(7, cfg)
    key = ref.root_key(7)
    for i in range(cfg["num_hidden_layers"]):
        w = ref.layer_weights(key, cfg, i)
        p = params["segments"][0]["p0"]
        assert np.array_equal(np.asarray(p["mixer"]["wq"][i]), np.asarray(w["wq"]))
        assert np.array_equal(np.asarray(p["mlp"]["w_down"][i]), np.asarray(w["w_down"]))
    assert np.array_equal(np.asarray(params["lm_head"]),
                          np.asarray(ref.head_weights(key, cfg)))
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: __import__("repro.models.lm", fromlist=["LM"]).LM(
            so.arch(cfg)).init(jax.random.key(0))))


# -- the cell end to end ---------------------------------------------------------
def test_rehearsal_prints_the_contract_line(tmp_path):
    line, _ = _run(tmp_path, ["--workload", CELL, "--seed", str(SEED),
                              "--seconds", "3", "--trace", "0"])
    _check_line(line, CELL, trace=False)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"ttft_p85_ms", "itl_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal(tmp_path):
    line, _ = _run(tmp_path, ["--workload", CELL, "--seed", str(SEED + 4),
                              "--seconds", "3", "--trace", "1"])
    _check_line(line, CELL, trace=True)
    assert line["correct"] is True, line["checks"]
    got = {k: m["value"] for k, m in line["metrics"].items()}
    # the CPU has no device plane: device metrics are left out, never 0
    assert not {"device_idle_share.serve", "mfu.serve",
                "decode_attention_roofline"} & set(got)
    assert 0 < got["reuse_share.serve"] < 100
    assert got["decode_batch_mean.serve"] >= 1
    assert got["programs_built.serve"] > 0


def test_precision_control_is_not_correct(tmp_path):
    """The reference at float8_e4m3fn operands, read at the same positions,
    fails a limit the program meets."""
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", CELL,
                        "--seed", str(SEED + 3), "--seconds", "3", "--mode",
                        "readings", "--rehearse", "--cache-dir", str(tmp_path / "c")],
                       cwd=str(ROOT), env=_env(tmp_path), capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["program_correct"] is True, got
    assert got["control_correct"] is False, got
    limits = _spec("workloads", CELL)["rehearse"]["check"]["limits"]
    assert any(got["control"][k] > v for k, v in limits.items())


@pytest.mark.parametrize("fault", ["token", "kv", "first_chunk"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    line, err = _run(tmp_path, [fault, "--workload", CELL, "--seed", str(SEED + 2),
                                "--seconds", "2", "--trace", "0"],
                     script=HERE / "faulty_serve.py")
    assert line["correct"] is False, err[-2000:]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    if fault == "first_chunk":
        window = json.loads(err.split("failed; ", 1)[1].splitlines()[0])
        assert window["cold_first_chunk_plans"] > 0


def test_the_windows_first_cold_first_chunk_plan_is_compared():
    """The first window request whose plan computes the first chunk alone
    before a stored segment keeps its logit rows and is compared beside the
    sample; a lead-in request and later ones are not."""
    from types import SimpleNamespace as NS

    so = _driver()
    cold = [NS(rng=NS(lo=0, hi=128), model_id=None),
            NS(rng=NS(lo=128, hi=512), model_id="s")]
    kinds = [cold, [NS(rng=NS(lo=0, hi=512), model_id="s")], cold, cold]

    class Mgr:
        def __init__(self):
            self.sessions = {}

        def add_session(self, doc):
            sid = len(self.sessions)
            self.sessions[sid] = NS(doc_id=f"d{sid}", logits=np.ones(3))
            return sid

        def submit(self, sid, n, n_new, greedy):
            return NS(steps=kinds[sid])

    srv = so.Server.__new__(so.Server)
    srv.docs, srv.mgr, srv.doc_ids = [np.ones(513, np.int32)], Mgr(), {}
    srv.cell, srv.cold_first, srv.witness = {"program": {"chunk_tokens": 128}}, 0, None
    lead = so.Request(-1.0, 0, 8)
    reqs = [so.Request(t, 0, 8, window=True) for t in (0.0, 1.0, 2.0)]
    picks = [reqs[0]]
    so.keep_rows(reqs[0])
    for r in (lead, *reqs):
        srv._submit(r, 0.0, 0.0)
    assert srv.cold_first == 3 and srv.witness is reqs[1]
    assert reqs[1].want == (0, 1, 4, 7) and 0 in reqs[1].logits
    assert not lead.want and not reqs[2].want
    assert [id(r) for r, _ in so.compared(srv, picks)] == [id(reqs[0]), id(reqs[1])]
    srv.witness = reqs[0]
    assert [id(r) for r, _ in so.compared(srv, picks)] == [id(reqs[0])]


# -- work counts, schedules, sizes ---------------------------------------------------
def test_serve_work_by_hand():
    """deepseek-67b-4L: d 8192, 64 heads over 8 KV heads of 128, F 22016,
    V 102400, 4 layers, bf16 KV."""
    from reference import serve as ref
    from work import serve as work

    n = ref.dims(_spec("configs", "deepseek-67b-4L"))
    # K and V: 2 x 8 heads x 128 x 2 B x 4 layers
    assert work.kv_bytes_per_token(n) == 16_384
    # decode, 3 rows at pos 99, 199, 299: 100 + 200 + 300 = 600 live keys.
    # ops: 4 x 64 x 128 = 32,768 per key and layer, x 600 x 4 = 78,643,200.
    # bytes: 600 keys x 4,096 B of K and V, plus 3 rows x 32,768 B of q and
    # out (2 x 64 x 128 x 2 B), = 2,555,904 per layer, x 4 = 10,223,616.
    assert work.decode_attention(n, 600, 3) == (78_643_200.0, 10_223_616.0)
    # extend of 128 positions from 1024: keys 128 x 1024 + 128 x 129 / 2 =
    # 139,328; ops 139,328 x 32,768 x 4 = 18,261,999,616.  bytes: 1152 live
    # positions x 4,096 + 128 x 32,768 = 8,912,896 per layer, x 4.
    assert work.extend_attention(n, 1024, 128) == (18_261_999_616.0, 35_651_584.0)
    # weights per token and layer: q 8192 x 8192 + k, v 2 x 8192 x 1024 +
    # o 8192 x 8192 + 3 x 8192 x 22016 = 692,060,160; x 2 ops x 4 layers
    assert work.matmul_flops_per_token(n) == 5_536_481_280.0
    assert work.head_flops(n) == 2.0 * 8192 * 102_400
    # a plan with a cold first gap and a stored middle: padding never counts
    assert work.build_calls([(0, 300), (512, 700)], 701, 128) == [
        ("prefill", 0, 128), ("extend", 128, 128), ("extend", 256, 44),
        ("extend", 512, 128), ("extend", 640, 60), ("extend", 700, 1)]


def test_cold_first_chunk_plans_are_named():
    """Only a plan that computes the first chunk alone, then reuses a
    stored segment, is counted; a longer cold start, a whole cold build and
    a plan that starts from the store are not."""
    from types import SimpleNamespace as NS

    so = _driver()

    def plan(*steps):
        return [NS(rng=NS(lo=lo, hi=hi), model_id=m) for lo, hi, m in steps]
    assert so.cold_first_chunk(plan((128, 256, "s1"), (0, 128, None)), 128)
    assert so.cold_first_chunk(plan((0, 64, None), (64, 256, "s1")), 128)
    assert not so.cold_first_chunk(plan((0, 256, None), (256, 384, "s1")), 128)
    assert not so.cold_first_chunk(plan((0, 128, None)), 128)
    assert not so.cold_first_chunk(plan((0, 128, "s0"), (128, 200, None)), 128)


def test_schedule_is_one_trace_at_fixed_quantiles():
    """The same requests at the same times for every seed: counts per
    document and answer lengths at fixed quantiles, due inside the window."""
    import arrivals
    import traffic

    tr = _spec("traffic", "docqa-shared")
    a = arrivals.schedule(tr, 3.0, 30, 24)
    assert a == arrivals.schedule(tr, 3.0, 30, 24)
    assert len(a) == 90 and a[0][0] == 0 and a[-1][0] < 30
    assert sorted(x[2] for x in a) == list(traffic.sizes(tr["answer_len"], 90))
    ranks = [x[1] for x in a]
    assert ranks.count(0) > ranks.count(1) > ranks.count(5) and max(ranks) < 130
    assert arrivals.schedule(tr, 3.0, 8, 23) != a[:24]      # the lead-in's own


def test_configuration_keeps_the_published_widths():
    """Every field of the program's 4-layer cut but the norm's epsilon,
    which the file takes from the source (1e-6) where the program's
    ``deepseek_67b.py`` keeps the default 1e-5."""
    from repro.configs import depth_cut, get_config

    so = _driver()
    cfg = _spec("configs", "deepseek-67b-4L")
    full = depth_cut(get_config("deepseek-67b"), 4)
    got = so.arch(cfg)
    assert got.norm_eps == 1e-6
    assert dataclasses.replace(got, name=full.name, norm_eps=full.norm_eps) == full


def _padded_pool_bytes(tr: dict, prog: dict, kv_bytes: int) -> int:
    """The pool's segments as the program stores them: whole chunks,
    the ragged end of each prompt build in ``segment_bucket`` positions."""
    so = _driver()
    chunk, seg = prog["chunk_tokens"], prog["segment_bucket"]
    padded = sum((L - 1) // chunk * chunk + -(-((L - 1) % chunk) // seg) * seg
                 for L in so.pool_lengths(tr))
    return int(padded) * kv_bytes


def test_pool_outgrows_its_store():
    """The workload's pool, stored as the program pads segments, is about
    1.5 times the store's budget: the store evicts, at the cell's size and
    in the rehearsal."""
    so = _driver()
    tr, cell = _spec("traffic", "docqa-shared"), _spec("workloads", CELL)
    cfg = _spec("configs", "deepseek-67b-4L")
    lens = so.pool_lengths(tr)
    assert len(lens) == 130
    assert lens.min() >= 1024 and lens.max() <= 3584 and not (lens % 64).any()
    prog = cell["program"]
    got = _padded_pool_bytes(tr, prog, so.work.kv_bytes_per_token(so.ref.dims(cfg)))
    assert got == 279_040 * 16_384
    assert 1.4 <= got / prog["byte_budget"] <= 1.6
    r = cell["rehearse"]
    tiny = so.work.kv_bytes_per_token(so.ref.dims(_tiny_cfg()))
    got = _padded_pool_bytes({**tr, **r["traffic"]}, r["program"], tiny)
    assert 1.4 <= got / r["program"]["byte_budget"] <= 1.6
