"""Serving driver: descriptor-planned prefix reuse, single- or multi-session.

Single session over one document:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 2048 --requests 8 --new-tokens 16

Multi-session batched serving (shared segment store, continuous batching):

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 1024 --sessions 6 --shared-docs 2 --requests 2 --new-tokens 8

Published widths on one chip: ``--layers N`` keeps every width and dtype
of the arch and cuts only its depth (``configs.depth_cut``); deepseek-67b
at 4 layers is ≈ 8.9 GB of bf16 weights:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --layers 4 \
      --doc-len 2048 --sessions 4 --requests 2 --new-tokens 16

Warm restarts: ``--store-dir`` makes the segment store durable — on
startup an existing snapshot is reloaded (the replayed traffic is served
from the warm segments instead of re-prefilled), ``--snapshot-every N``
re-snapshots after every N request rounds, and a final snapshot is always
taken on exit.  Snapshots are atomic (temp dir + rename), so a crash
mid-snapshot leaves the previous complete snapshot in place:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 1024 --sessions 4 --requests 2 --store-dir /tmp/kvstore \
      --snapshot-every 1

Tiered residency: ``--host-budget`` / ``--spill-dir`` open host-RAM and
disk tiers below the device budget, so segments squeezed out by
``--byte-budget`` demote (cost-priced) instead of being rebuilt from
scratch; ``--tier-policy evict`` restores the old drop-only behavior.
Periodic snapshots run on a background writer by default
(``--sync-saves`` to disable); ``--compact-final`` rewrites the snapshot
directory compactly on exit:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 1024 --sessions 4 --requests 2 --byte-budget 50000000 \
      --host-budget 500000000 --spill-dir /tmp/kvspill --store-dir /tmp/kvstore

Sharded serving: ``--shards N`` spreads the store over N consistent-hash
shards (simulated in-process hosts, each with its own device/host/disk
tiers at the configured per-shard budgets).  Documents homed on a remote
shard are fetched over a simulated wire (``--shard-bw``/``--shard-rtt``),
coalesced one transfer per shard per scheduler tick, int8-quantized and
deflated on the wire; fetches past ``--hedge-deadline`` race a backup
local rebuild (first done wins):

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 1024 --sessions 4 --requests 2 --shards 2 \
      --byte-budget 50000000

Edit traffic: ``--edit-every N`` mutates each session's document after
every N request rounds (insert/delete/replace at a random offset) and
serves the edited text via the delta-update path — stored segments before
the divergence point are rekeyed to the edited content, the rest released
from every tier:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-67b --reduced \
      --doc-len 1024 --sessions 4 --requests 4 --edit-every 1
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache


def _tier_kwargs(args) -> dict:
    """Residency-tier / precision configuration from the command line
    (empty = legacy single-tier fp32-pinned-by-default store, byte-for-
    byte the pre-tier behavior)."""
    kw = {}
    if args.host_budget > 0:
        kw["host_budget"] = args.host_budget
    if args.spill_dir:
        kw["spill_dir"] = args.spill_dir
    if args.tier_policy:
        kw["tier_policy"] = args.tier_policy
    if args.segment_precision:
        kw["precision"] = args.segment_precision
    return kw


def _load_store(args, budget, tiers):
    """Reload the segment store from ``--store-dir`` if a snapshot exists.

    Documents are content-keyed everywhere (including single-session mode,
    see :func:`run_single`), so a snapshot taken over different documents
    simply yields no hits rather than stale KV.  Model parameters are
    *not* part of segment identity: a snapshot is only valid for the
    (arch, seed) it was taken under.
    """
    if not args.store_dir:
        return None
    if args.shards > 1:
        from repro.serve.shard_store import ShardedSegmentStore

        try:
            store = ShardedSegmentStore.load(
                args.store_dir, n_shards=args.shards, byte_budget=budget,
                policy=args.eviction_policy,
                bw_bytes_per_s=args.shard_bw, rtt_s=args.shard_rtt,
                hedge_deadline_s=args.hedge_deadline, **tiers)
        except (FileNotFoundError, IOError):
            return None   # no snapshot yet: first run populates it
        print(f"warm start: reloaded {store.total_segments()} segments "
              f"({store.total_nbytes()/1e6:.1f} MB, "
              f"{len(store.doc_ids())} documents, {store.n_shards} shards) "
              f"from {args.store_dir}")
        return store
    from repro.serve.kv_cache import SegmentStore

    try:
        store = SegmentStore.load(args.store_dir, byte_budget=budget,
                                  policy=args.eviction_policy, **tiers)
    except FileNotFoundError:
        return None       # no snapshot yet: first run populates it
    print(f"warm start: reloaded {len(store)} segments "
          f"({store.nbytes()/1e6:.1f} MB, {len(store.doc_ids())} documents) "
          f"from {args.store_dir}")
    return store


def _make_store(args, budget, seq_bucket):
    """Load-or-create the store when launch-level config demands it.

    Returns ``None`` on the legacy path (no snapshot, no tier flags) so
    the engine/manager construct their own store exactly as before; the
    tier flags force construction here because they are store-creation
    parameters, same contract as ``byte_budget``.
    """
    tiers = _tier_kwargs(args)
    store = _load_store(args, budget, tiers)
    if store is not None:
        return store
    if args.shards > 1:
        # sharded serving always constructs here: shard count, wire
        # calibration, and hedging are store-creation parameters
        from repro.core.cost import serve_cost_model
        from repro.serve.shard_store import ShardedSegmentStore

        return ShardedSegmentStore(
            args.shards, byte_budget=budget, cost_model=serve_cost_model(),
            policy=args.eviction_policy, seq_bucket=seq_bucket,
            bw_bytes_per_s=args.shard_bw, rtt_s=args.shard_rtt,
            hedge_deadline_s=args.hedge_deadline, **tiers)
    if not tiers:
        return None
    from repro.core.cost import serve_cost_model
    from repro.serve.kv_cache import SegmentStore

    return SegmentStore(byte_budget=budget, cost_model=serve_cost_model(),
                        policy=args.eviction_policy, seq_bucket=seq_bucket,
                        **tiers)


def _snapshot(store, args, *, final: bool = False) -> None:
    if not args.store_dir:
        return
    if not final:
        # periodic snapshots ride the background writer (coalesced if one
        # is already in flight) so the serving loop never blocks on I/O
        if args.background_saves:
            store.save_async(args.store_dir)
        else:
            store.save(args.store_dir)
        return
    # the final snapshot is synchronous — restart-equals-warm requires the
    # complete store on disk before exit (save() drains queued writes first)
    store.save(args.store_dir)
    if args.compact_final:
        res = store.compact_snapshot()
        if res is not None:
            print(f"compacted snapshot: kept {res['kept']}, "
                  f"dropped {res['dropped']}")
    print(f"snapshot: {len(store)} segments ({store.nbytes()/1e6:.1f} MB) "
          f"-> {args.store_dir}")


def _print_tier_report(store, args) -> None:
    tiers = store.tier_bytes()
    print(f"  tiers ({store.tier_policy} policy): "
          f"device {tiers['device']/1e6:.1f} MB, "
          f"host {tiers['host']/1e6:.1f} MB, "
          f"disk {tiers['disk']/1e6:.1f} MB")
    print(f"  tier traffic: promotions {sum(store.promotions.values())} "
          f"(host {store.promotions['host']}, disk {store.promotions['disk']}), "
          f"demotions {sum(store.demotions.values())} "
          f"(host {store.demotions['host']}, disk {store.demotions['disk']}), "
          f"prefetches {store.prefetches}, spill writes {store.spill_writes}")
    print(f"  precision ({store.precision} policy): "
          f"{store.quantized_segments()} int8 segments resident, "
          f"{store.quantized} quantized, "
          f"{store.quant_bytes_saved/1e6:.1f} MB saved")
    if args.store_dir:
        w = store.writer
        print(f"  background saves: {store.bg_saves} completed, "
              f"{store.bg_save_drops} coalesced, "
              f"queue {w.depth() if w is not None else 0}, "
              f"stall {store.save_stall_s*1e3:.1f} ms, "
              f"errors {len(store.save_errors)}")


def _print_shard_report(st) -> None:
    """Per-shard occupancy and fetch-traffic lines (sharded stores only;
    the smoke test regexes these)."""
    if not hasattr(st, "shard_summaries"):
        return
    rep = st.shard_report()
    print(f"  fetch traffic ({rep['shards']} shards): "
          f"{rep['remote_fetches']} segments fetched "
          f"({rep['remote_fetch_wire_bytes']/1e6:.1f} MB wire) over "
          f"{rep['remote_transfers']} transfers, "
          f"{rep['fetched_hits']} fetched hits, "
          f"{rep['on_demand_fetches']} on-demand, "
          f"{rep['coalesce_violations']} coalesce violations")
    print(f"  hedging: {rep['hedged_fetches']} hedged "
          f"({rep['hedge_rebuild_wins']} rebuild wins, "
          f"{rep['hedge_fetch_wins']} fetch wins, "
          f"{rep['cancelled_fetches']} fetches cancelled), "
          f"{rep['dead_shard_skips']} dead-shard skips, "
          f"{rep['put_forwards']} put-forwards "
          f"({rep['put_forward_bytes']/1e6:.1f} MB)")
    for s in st.shard_summaries():
        print(f"  shard {s['shard']}: {s['segments']} segments, "
              f"device {s['device_bytes']/1e6:.1f} MB, "
              f"host {s['host_bytes']/1e6:.1f} MB, "
              f"disk {s['disk_bytes']/1e6:.1f} MB, "
              f"{s['hits']} hits, {s['evictions']} evictions, "
              f"{s['docs']} docs")


def _extras(cfg):
    extras = {}
    if cfg.encoder_layers:
        import jax.numpy as jnp

        extras["enc_feats"] = jnp.zeros((1, cfg.encoder_context, cfg.d_model))
    if cfg.vision_context:
        import jax.numpy as jnp

        extras["image_embeds"] = jnp.zeros((1, cfg.vision_context, cfg.d_model))
    return extras


def run_single(args, cfg, model, params, rng):
    from repro.serve.engine import ServeEngine

    doc = rng.integers(0, cfg.vocab_size, args.doc_len).astype(np.int32)
    budget = args.byte_budget if args.byte_budget > 0 else None
    store = _make_store(args, budget, 64)   # ServeEngine's seq_bucket default
    store_kw = (dict(store=store) if store is not None
                else dict(byte_budget=budget,
                          eviction_policy=args.eviction_policy))
    extras = _extras(cfg)
    # content-keyed doc_id (not the historical constant "doc"): a durable
    # snapshot reloaded against a different document must miss, not serve
    # the previous document's KV
    from repro.serve.session import doc_key

    eng = ServeEngine(model, params, doc, extras=extras,
                      chunk_tokens=args.chunk_tokens,
                      doc_id=doc_key(doc, extras), **store_kw)
    for i in range(args.requests):
        L = int(rng.integers(args.doc_len // 4, args.doc_len))
        toks, plan = eng.generate(L, args.new_tokens, greedy=False, seed=i)
        print(f"req {i}: prefix {L:6d}  reused-models {len(plan.models_used):3d}  "
              f"tokens {toks[:8]}…")
        if args.snapshot_every and (i + 1) % args.snapshot_every == 0:
            _snapshot(eng.store, args)
    _snapshot(eng.store, args, final=True)
    s = eng.stats
    print(f"\n{s.requests} requests: reuse {s.reuse_frac:.1%} "
          f"({s.tokens_reused} reused / {s.tokens_computed} computed), "
          f"planner {s.planner_s*1e3:.1f} ms total, prefill {s.prefill_s:.2f}s, "
          f"decode {s.decode_s:.2f}s, store {len(eng.store)} segments "
          f"({eng.store.nbytes()/1e6:.1f} MB)")
    _print_tier_report(eng.store, args)
    _print_shard_report(eng.store)
    return eng


def run_multi(args, cfg, model, params, rng):
    from repro.serve.session import SessionManager

    n_shared = min(max(args.shared_docs, 0), args.sessions)
    shared_doc = rng.integers(0, cfg.vocab_size, args.doc_len).astype(np.int32)
    unique_docs = [rng.integers(0, cfg.vocab_size, args.doc_len).astype(np.int32)
                   for _ in range(args.sessions - n_shared)]
    budget = args.byte_budget if args.byte_budget > 0 else None
    store = _make_store(args, budget, args.chunk_tokens)  # = decode_bucket
    store_kw = (dict(store=store) if store is not None
                else dict(byte_budget=budget,
                          eviction_policy=args.eviction_policy))
    mgr = SessionManager(model, params, chunk_tokens=args.chunk_tokens,
                         decode_bucket=args.chunk_tokens,
                         max_batch=args.max_batch,
                         decode_materialize=not args.no_decode_materialize,
                         async_prefill=args.async_prefill,
                         **store_kw)
    extras = _extras(cfg)
    # the first `n_shared` sessions all serve one document; the rest get unique docs
    sids = []
    for i in range(args.sessions):
        doc = shared_doc if i < n_shared else unique_docs[i - n_shared]
        sids.append(mgr.add_session(doc, extras=dict(extras)))

    edit_reused = edit_rebuilt = 0
    t0 = time.perf_counter()
    for r in range(args.requests):
        for i, sid in enumerate(sids):
            dl = len(mgr.sessions[sid].doc)
            L = int(rng.integers(max(dl // 4, 1), max(dl, 2)))
            plan = mgr.submit(sid, L, args.new_tokens, greedy=False,
                              seed=r * 1000 + i)
            assert plan.validate_telescoping()
        mgr.run()
        if args.edit_every and (r + 1) % args.edit_every == 0:
            # edit traffic: each session's document mutates mid-stream and
            # the store keeps every segment before the divergence point
            from repro.data.edits import EDIT_KINDS, random_edit

            kinds = (EDIT_KINDS if args.edit_kind == "random"
                     else (args.edit_kind,))
            for sid in sids:
                doc = mgr.sessions[sid].doc
                new_doc, _, _, _ = random_edit(
                    rng, doc, cfg.vocab_size, kinds=kinds,
                    max_span=args.edit_span, min_offset=len(doc) // 4)
                eplan = mgr.update_document(sid, new_doc)
                edit_reused += eplan.reused_tokens
                edit_rebuilt += eplan.rebuild_tokens
        if args.snapshot_every and (r + 1) % args.snapshot_every == 0:
            _snapshot(mgr.store, args)
    wall = time.perf_counter() - t0
    _snapshot(mgr.store, args, final=True)

    agg = mgr.aggregate_stats()
    st = mgr.store
    print(f"{args.sessions} sessions × {args.requests} requests "
          f"({n_shared} on a shared doc):")
    print(f"  aggregate: {agg.tokens_decoded} tokens decoded, "
          f"{agg.tokens_decoded / wall:.1f} tok/s wall, reuse {agg.reuse_frac:.1%} "
          f"({agg.tokens_reused} reused / {agg.tokens_computed} computed)")
    print(f"  store: {len(st)} segments, {st.nbytes()/1e6:.1f} MB, "
          f"{st.evictions} evictions ({st.policy} policy), "
          f"{st.cross_session_hits} cross-session hits")
    print(f"  scheduler: {mgr.sched.decode_calls} batched decode calls, "
          f"mean batch {mgr.sched.mean_batch:.2f}, "
          f"{mgr.sched.pack_rebuilds} pack rebuilds")
    print(f"  decode materialization: {mgr.sched.decode_segments} segments "
          f"admitted, {mgr.sched.decode_rejects} rejected")
    rep = mgr.report()   # guarded: finite even on an idle/zero-traffic run
    packing = "merged ragged" if mgr.merge_decode_packs else "capacity-split"
    print(f"  attention routes: decode {mgr.decode_mode}, "
          f"extend {mgr.extend_mode}")
    print(f"  decode packs ({packing}, {mgr.decode_mode} attention): "
          f"padded occupancy {rep['decode_padded_frac']:.1%} "
          f"({rep['decode_valid_tokens']} valid / "
          f"{rep['decode_padded_tokens']} padded KV tokens), "
          f"attn ~{rep['decode_attn_flops']/1e9:.3f} GFLOP")
    mode = "async" if mgr.async_prefill else "sync"
    print(f"  pipeline ({mode} prefill): {rep['tickets_launched']} builds "
          f"launched, {rep['tickets_joined']} joined "
          f"(mean join wait {rep['mean_join_wait_s']*1e3:.1f} ms), "
          f"{rep['overlap_steps']} decode rounds overlapped builds "
          f"(mean batch {rep['overlap_batch']:.2f})")
    if args.edit_every:
        sc = mgr.sched
        tot = edit_reused + edit_rebuilt
        print(f"  edits: {sc.edits} applied, "
              f"{sc.edit_reused_segments} segments rekeyed, "
              f"{sc.edit_orphaned} orphaned, "
              f"{sc.edit_cancelled} requests cancelled, "
              f"reused {edit_reused}/{tot} planned tokens "
              f"({edit_reused / tot if tot else 0.0:.1%})")
    _print_tier_report(st, args)
    _print_shard_report(st)
    if args.store_dir and st.last_save:
        print(f"  snapshot: {st.last_save['written']} entries written, "
              f"{st.last_save['reused']} reused from the previous snapshot")
    return mgr


def _check_saves(store) -> None:
    """Fail the run if any background snapshot failed.

    The writer thread records failures in ``save_errors`` instead of
    raising into the serving loop; this is where they surface, after the
    final save, so a run whose snapshots were lost never exits 0.
    """
    shards = [store, *getattr(store, "remotes", ())]
    errors = [e for st in shards for e in st.save_errors]
    if errors:
        raise SystemExit(f"{len(errors)} background snapshot save(s) "
                         f"failed; first: {errors[0]!r}")


def main(argv=None):
    """Parse ``argv`` (default: the command line), serve, and return the
    ``SessionManager`` (``--sessions`` > 1) or ``ServeEngine`` that ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep every published width and dtype but only N "
                         "layers (whole structural periods; 0 = all)")
    ap.add_argument("--doc-len", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--chunk-tokens", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sessions", type=int, default=1,
                    help=">1 switches to the multi-session batched engine")
    ap.add_argument("--shared-docs", type=int, default=2,
                    help="how many sessions serve the same document")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--byte-budget", type=int, default=0,
                    help="global segment-store budget in bytes (0 = unbounded)")
    ap.add_argument("--eviction-policy", choices=["cost", "lru"], default=None,
                    help="victim selection under --byte-budget: cost-model "
                         "benefit-per-byte (default) or legacy global LRU")
    ap.add_argument("--no-decode-materialize", action="store_true",
                    help="disable writing decode-generated KV back into the "
                         "segment store")
    ap.add_argument("--async-prefill", dest="async_prefill",
                    action="store_true", default=None,
                    help="pipeline prefix builds with decode (default): "
                         "submit launches the build asynchronously and warm "
                         "sessions keep decoding until the cold session "
                         "joins before its first decode")
    ap.add_argument("--sync-prefill", dest="async_prefill",
                    action="store_false",
                    help="monolithic loop: every submit blocks all decoding "
                         "sessions until its prefix build completes "
                         "(bitwise-identical tokens and store contents)")
    ap.add_argument("--edit-every", type=int, default=0,
                    help="multi-session edit traffic: after every N request "
                         "rounds, mutate each session's document in place "
                         "(insert/delete/replace) and serve the edited text "
                         "via the delta-update path — segments before the "
                         "divergence point are rekeyed, the rest released "
                         "(0 = no edits)")
    ap.add_argument("--edit-kind", choices=["insert", "delete", "replace",
                                            "random"], default="random",
                    help="which edit operation --edit-every applies")
    ap.add_argument("--edit-span", type=int, default=16,
                    help="maximum tokens one edit inserts/deletes/replaces")
    ap.add_argument("--store-dir", default="",
                    help="directory for durable segment-store snapshots; an "
                         "existing snapshot is reloaded on startup (warm "
                         "restart) and a final snapshot is written on exit. "
                         "Documents are content-keyed, but the snapshot is "
                         "only valid for the model (arch/seed) it was taken "
                         "under")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="with --store-dir: re-snapshot the store every N "
                         "request rounds (0 = only on exit)")
    ap.add_argument("--host-budget", type=int, default=0,
                    help="host-RAM tier capacity in bytes (0 = tier "
                         "disabled): segments squeezed out of the device "
                         "budget demote here when the cost model prices the "
                         "round-trip below a rebuild")
    ap.add_argument("--spill-dir", default="",
                    help="directory for the disk tier's spill files (empty "
                         "= tier disabled); overflow from the host tier "
                         "spills here via the background writer")
    ap.add_argument("--tier-policy", choices=["tiered", "evict"], default=None,
                    help="under byte pressure: cost-priced demotion through "
                         "the residency tiers (default) or legacy "
                         "evict-only drops (default honors "
                         "REPRO_TIER_POLICY)")
    ap.add_argument("--segment-precision", choices=["auto", "fp32", "int8"],
                    default=None,
                    help="stored-segment precision: 'auto' lets the cost "
                         "model quantize long-tail segments to blockwise "
                         "int8 under pressure (engaged with the tier "
                         "ladder), 'fp32' pins everything lossless (the "
                         "pre-precision behavior, also via "
                         "REPRO_SEGMENT_PRECISION=fp32), 'int8' quantizes "
                         "every admitted segment")
    ap.add_argument("--shards", type=int, default=1,
                    help=">1 spreads the segment store over N consistent-"
                         "hash shards (simulated in-process hosts); "
                         "--byte-budget/--host-budget/--spill-dir apply "
                         "per shard, and remote-homed documents are served "
                         "by coalesced, hedged wire fetches")
    ap.add_argument("--shard-bw", type=float, default=2e9,
                    help="simulated cross-shard wire bandwidth in bytes/s "
                         "(calibrates both the cost model's fetch pricing "
                         "and the transport's transfer clock)")
    ap.add_argument("--shard-rtt", type=float, default=1e-3,
                    help="simulated cross-shard round-trip latency in "
                         "seconds (amortized across a coalesced batch)")
    ap.add_argument("--hedge-deadline", type=float, default=None,
                    help="estimated-fetch-seconds threshold past which a "
                         "remote fetch races a backup local rebuild, first "
                         "done wins (default honors REPRO_HEDGE_DEADLINE, "
                         "then 0.05)")
    ap.add_argument("--background-saves", dest="background_saves",
                    action="store_true", default=True,
                    help="run --snapshot-every saves on the background "
                         "writer (default): serialization never blocks a "
                         "decode step, and overlapping requests coalesce")
    ap.add_argument("--sync-saves", dest="background_saves",
                    action="store_false",
                    help="write every periodic snapshot on the serving "
                         "thread (the final snapshot is always synchronous)")
    ap.add_argument("--compact-final", action="store_true",
                    help="after the final snapshot: rewrite the snapshot "
                         "dir compactly (drops stranded files and "
                         "hard-link chains from older generations)")
    args = ap.parse_args(argv)

    from repro.configs import depth_cut, get_config, reduced
    from repro.models.lm import LM

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.layers:
        cfg = depth_cut(cfg, args.layers)
    model = LM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(args.seed)))
    leaves = jax.tree.leaves(params)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"model {cfg.name}: {sum(x.size for x in leaves) / 1e9:.2f}B params, "
          f"{sum(x.nbytes for x in leaves) / 1e9:.2f} GB {cfg.param_dtype} on "
          f"{dev.device_kind}, init {time.perf_counter() - t0:.1f} s"
          + (f", device peak {peak / 1e9:.2f} GB" if peak else ""), flush=True)
    rng = np.random.default_rng(args.seed)
    run = run_multi if args.sessions > 1 else run_single
    server = run(args, cfg, model, params, rng)
    _check_saves(server.store)
    return server


if __name__ == "__main__":
    main()
