#!/usr/bin/env python
"""Sharded-serving smoke: two simulated shards under byte pressure.

Two gates, in-process and subprocess:

  * In-process: a 2-shard ``ShardedSegmentStore`` serves balanced traffic
    (half the documents homed on the remote shard) under per-shard byte
    pressure and must (a) serve cross-shard hits over coalesced fetches
    — one transfer per contacted shard per tick, zero violations; (b)
    stream bit-identically to a single-shard unbounded reference; (c)
    hedge against an injected straggler — after the slowdown is observed,
    the fetch estimate blows the deadline and the backup local rebuild
    wins the race.
  * Subprocess: ``repro.launch.serve --shards 2`` (the exact artifact a
    deployment runs) must emit the per-shard report lines, route writes
    to their home shards, and leave a per-shard snapshot tree that
    ``ShardedSegmentStore.load`` verifies clean.

Run from the repo root:  PYTHONPATH=src python scripts/sharded_smoke.py
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _balanced_docs(rng, vocab, doc_len, n_docs, n_shards):
    from repro.serve.session import doc_key
    from repro.serve.shard_store import HashRing

    ring = HashRing(n_shards)
    quota = {s: n_docs // n_shards for s in range(n_shards)}
    docs = []
    while len(docs) < n_docs:
        doc = rng.integers(0, vocab, doc_len).astype("int32")
        home = ring.place(doc_key(doc, {}))
        if quota.get(home, 0) > 0:
            quota[home] -= 1
            docs.append(doc)
    return docs


def _replay(mgr, docs, *, rounds, n_new=2, seed0=0):
    sids = [mgr.add_session(d) for d in docs]
    streams = []
    for r in range(rounds):
        mgr.submit_many([(sid, len(docs[i]), n_new, seed0 + r * 100 + i)
                         for i, sid in enumerate(sids)])
        toks = mgr.run()
        streams.append(tuple(tuple(toks[sid]) for sid in sids))
    return streams


def in_process() -> None:
    import jax
    import numpy as np

    from repro.configs import ARCHS, reduced
    from repro.core.cost import serve_cost_model
    from repro.models.lm import LM
    from repro.serve.session import SessionManager
    from repro.serve.shard_store import ShardedSegmentStore

    cfg = reduced(ARCHS["deepseek-67b"])
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    docs = _balanced_docs(rng, cfg.vocab_size, 160, 4, 2)

    mk = lambda store=None: SessionManager(
        model, params, chunk_tokens=32, decode_bucket=32,
        decode_materialize=False, store=store)

    # single-shard unbounded reference pins the token streams
    probe = mk()
    ref = _replay(probe, docs, rounds=3)
    budget = max(int(probe.store.nbytes() * 0.5), 1)   # per-shard pressure

    mgr = mk(ShardedSegmentStore(2, byte_budget=budget,
                                 cost_model=serve_cost_model(),
                                 seq_bucket=32))
    st = mgr.store
    got = _replay(mgr, docs, rounds=3)
    assert got == ref, (
        "2-shard streams diverged from the single-shard unbounded "
        "reference — a remote fetch perturbed a served token")
    assert st.remote_fetches > 0, "no cross-shard fetches under pressure"
    assert st.fetched_hits > 0, "fetched segments never served the builder"
    assert st.transport.coalesce_violations == 0, (
        f"{st.transport.coalesce_violations} ticks broke the one-transfer-"
        f"per-shard contract")
    assert st.transport.max_transfers_per_shard_tick <= 1, (
        "a shard saw more than one transfer in one tick")

    # inject a straggler on the remote shard: the first post-injection
    # transfer observes the slowdown, after which the estimate blows the
    # hedge deadline and the backup local rebuild wins the race — and the
    # streams must STILL match the reference (a rebuild is exact)
    st.hedge_deadline_s = 0.05
    st.transport.slowdown[1] = 1e6
    got2 = _replay(mgr, docs, rounds=2, seed0=300)
    ref2 = _replay(probe, docs, rounds=2, seed0=300)
    assert st.hedged_fetches > 0, (
        "injected straggler never triggered a hedged fetch")
    assert st.hedge_rebuild_wins > 0, (
        "the local rebuild never won the hedge race against a 1e6x "
        "slowdown")
    assert got2 == ref2, "post-hedge streams diverged from the reference"
    print(f"sharded_smoke[in-process]: OK — {st.remote_fetches} fetches "
          f"({st.fetched_hits} hits) over {st.transport.transfers} "
          f"transfers, {st.hedged_fetches} hedged "
          f"({st.hedge_rebuild_wins} rebuild wins)")


def subprocess_launch() -> None:
    with tempfile.TemporaryDirectory() as d:
        store_dir = Path(d) / "kvstore"
        cmd = [
            sys.executable, "-m", "repro.launch.serve",
            "--arch", "deepseek-67b", "--reduced",
            "--doc-len", "256", "--sessions", "4", "--shared-docs", "0",
            "--requests", "2", "--new-tokens", "4",
            "--shards", "2", "--shard-rtt", "1e-6",
            "--store-dir", str(store_dir),
        ]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              env={**os.environ})
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        assert proc.returncode == 0, f"serve exited {proc.returncode}"

        m = re.search(r"fetch traffic \((\d+) shards\): (\d+) segments "
                      r"fetched", proc.stdout)
        assert m, "no fetch-traffic report line in serve output"
        assert int(m.group(1)) == 2, f"expected 2 shards, got {m.group(1)}"
        m = re.search(r"(\d+) coalesce violations", proc.stdout)
        assert m and int(m.group(1)) == 0, "coalescing contract broken"
        m = re.search(r"(\d+) put-forwards", proc.stdout)
        assert m and int(m.group(1)) > 0, (
            "no writes routed to the remote home shard")
        shard_lines = re.findall(r"shard (\d+): (\d+) segments", proc.stdout)
        assert {s for s, _ in shard_lines} == {"0", "1"}, (
            f"expected per-shard report lines for shards 0 and 1, "
            f"got {shard_lines}")
        assert all(int(n) > 0 for _, n in shard_lines), (
            "a shard ended the run empty — placement routed nothing to it")

        # the final snapshot tree (shard-00/, shard-01/) must load clean
        from repro.serve.shard_store import ShardedSegmentStore

        store = ShardedSegmentStore.load(store_dir)
        assert store.n_shards == 2, f"snapshot loaded {store.n_shards} shards"
        assert store.total_segments() > 0, "final snapshot is empty"
        print(f"sharded_smoke[subprocess]: OK — snapshot reloads "
              f"{store.total_segments()} segments over {store.n_shards} "
              f"shards clean")


def main() -> int:
    # a CPU gate: pin this process and the serve child it starts to the
    # CPU, so neither takes (or waits on) an accelerator the other holds
    os.environ["JAX_PLATFORMS"] = "cpu"
    in_process()
    subprocess_launch()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
