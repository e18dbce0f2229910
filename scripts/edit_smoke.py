#!/usr/bin/env python
"""Delta-update smoke: serve, edit mid-session, and gate the two contracts
that make edits worth shipping — the rebuild *reuses* stored segments
(reuse > 0) and the edited stream is *bit-identical* to a from-scratch
build of the edited text.

Two phases:

  1. in-process: one session serves a document, the document is edited at
     75% depth via ``SessionManager.update_document``, and the follow-up
     request's stream is compared token-for-token against a fresh manager
     built directly over the edited document;
  2. subprocess: the launch driver runs with ``--edit-every 1`` (the exact
     artifact a deployment runs) and its edit-report line must show
     applied edits with rekeyed segments and planned-token reuse.

Run from the repo root:  PYTHONPATH=src python scripts/edit_smoke.py
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def in_process_parity() -> None:
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models.lm import LM
    from repro.serve.session import SessionManager

    cfg = reduced(get_config("deepseek-67b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    doc = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 256).astype(np.int32)

    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=32)
    sid = mgr.add_session(doc)
    mgr.submit(sid, 256, 4)
    mgr.run()

    new_doc = doc.copy()                      # mid-document edit at 75% depth
    new_doc[192] = (new_doc[192] + 1) % cfg.vocab_size
    ep = mgr.update_document(sid, new_doc)
    assert ep.action == "edit", f"planner chose {ep.action} for a deep edit"
    assert ep.reused_tokens > 0, "edit plan reused nothing"
    assert ep.rebuild_frac <= 0.30, (
        f"75%-depth edit rebuilt {ep.rebuild_frac:.0%} of the document")
    mgr.submit(sid, 256, 8)
    edited = mgr.run()[sid]
    assert mgr.sessions[sid].stats.tokens_reused >= ep.reused_tokens, (
        "serve after edit did not reuse the rekeyed prefix")

    scratch = SessionManager(model, params, chunk_tokens=32, decode_bucket=32)
    sid2 = scratch.add_session(new_doc)
    scratch.submit(sid2, 256, 8)
    ref = scratch.run()[sid2]
    assert edited == ref, (
        f"edited stream diverged from scratch: {edited} vs {ref}")
    print(f"edit_smoke[in-process]: OK — reuse {ep.reused_tokens}/{ep.length} "
          f"tokens ({ep.rebuild_frac:.0%} rebuilt), stream bit-identical")


def driver_edit_traffic() -> None:
    cmd = [
        sys.executable, "-m", "repro.launch.serve",
        "--arch", "deepseek-67b", "--reduced",
        "--doc-len", "512", "--sessions", "3", "--shared-docs", "1",
        "--requests", "3", "--new-tokens", "4", "--chunk-tokens", "64",
        "--edit-every", "1", "--edit-kind", "replace",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, f"serve exited {proc.returncode}"

    m = re.search(r"edits: (\d+) applied, (\d+) segments rekeyed", proc.stdout)
    assert m, "no edit report line in serve output"
    edits, rekeyed = int(m.group(1)), int(m.group(2))
    assert edits > 0, "edit traffic applied no edits"
    assert rekeyed > 0, "edits rekeyed no segments — the delta path never engaged"
    m = re.search(r"reused (\d+)/(\d+) planned tokens", proc.stdout)
    assert m and int(m.group(1)) > 0, "edit plans reused no tokens"
    print(f"edit_smoke[driver]: OK — {edits} edits, {rekeyed} segments "
          f"rekeyed, {m.group(1)}/{m.group(2)} planned tokens reused")


def main() -> int:
    # a CPU gate: pin this process and the serve child it starts to the
    # CPU, so neither takes (or waits on) an accelerator the other holds
    os.environ["JAX_PLATFORMS"] = "cpu"
    in_process_parity()
    driver_edit_traffic()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
