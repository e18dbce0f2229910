#!/usr/bin/env python
"""Ragged decode-pack smoke: merged mixed-capacity decode, end to end.

Two gates, in-process and subprocess:

  * In-process: mixed short/long sessions decoding together.  Under the
    ragged blocked path (``REPRO_DECODE_KERNEL=auto`` on CPU) the
    scheduler must merge every session into ONE pack per round — fewer
    decode calls than the capacity-split dense baseline — while the
    token streams stay exactly identical and the padded-occupancy /
    attention-FLOP counters report sane (finite, in-range) values.
  * Subprocess: ``repro.launch.serve`` (the exact artifact a deployment
    runs) must print the decode-pack report line in both routing modes,
    naming the packing policy its env var selected.

Run from the repo root:  PYTHONPATH=src python scripts/decode_smoke.py
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

SHORT, LONG = 64, 160


def _run(mode_env: str):
    import jax
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.models.lm import LM
    from repro.serve.session import SessionManager

    os.environ["REPRO_DECODE_KERNEL"] = mode_env
    cfg = reduced(ARCHS["deepseek-67b"])
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (SHORT, SHORT, LONG)]
    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=32,
                         async_prefill=False, decode_materialize=False)
    sids = [mgr.add_session(d) for d in docs]
    for sid, doc in zip(sids, docs):
        mgr.submit(sid, len(doc), 6, seed=sid)
    out = mgr.run()
    rep = mgr.report()
    return [out[sid] for sid in sids], rep, mgr


def in_process() -> None:
    streams_ragged, rep_ragged, mgr_ragged = _run("auto")
    streams_dense, rep_dense, mgr_dense = _run("0")

    assert mgr_ragged.merge_decode_packs and mgr_ragged.decode_mode == "blocked", \
        f"auto on CPU must merge+block, got {mgr_ragged.decode_mode}"
    assert not mgr_dense.merge_decode_packs and mgr_dense.decode_mode == "dense"
    assert streams_ragged == streams_dense, \
        "merged ragged streams diverged from the capacity-split dense baseline"
    calls_r = rep_ragged["decode_calls"]
    calls_d = rep_dense["decode_calls"]
    assert calls_r < calls_d, \
        f"merging must cut decode calls: merged={calls_r} split={calls_d}"
    frac = rep_ragged["decode_padded_frac"]
    assert 0.0 < frac < 1.0, f"padded occupancy out of range: {frac}"
    assert rep_ragged["decode_attn_flops"] > 0.0
    print(f"in-process OK: calls merged={calls_r} < split={calls_d}, "
          f"occupancy {frac:.2f}, identical streams")


def subprocess_gate() -> None:
    repo = Path(__file__).resolve().parents[1]
    for env_val, expect in (("auto", "merged ragged"),
                            ("0", "capacity-split")):
        env = dict(os.environ, PYTHONPATH="src", REPRO_DECODE_KERNEL=env_val)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "deepseek-67b", "--reduced", "--doc-len", "96", "--sessions",
             "3", "--requests", "1", "--new-tokens", "4",
             "--chunk-tokens", "32"],
            cwd=repo, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = next((ln for ln in proc.stdout.splitlines()
                     if "decode packs" in ln), None)
        assert line is not None, \
            f"serve driver printed no decode-pack report:\n{proc.stdout}"
        assert expect in line, f"expected '{expect}' in: {line}"
        print(f"subprocess OK ({env_val}): {line.strip()}")


def main() -> None:
    # a CPU gate: pin this process and the serve child it starts to the
    # CPU, so neither takes (or waits on) an accelerator the other holds
    os.environ["JAX_PLATFORMS"] = "cpu"
    in_process()
    subprocess_gate()
    print("decode smoke OK")


if __name__ == "__main__":
    main()
