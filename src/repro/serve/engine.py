"""Serving engine: batched decode with descriptor-planned prefix reuse.

A request for a model over ``[0, L)`` — i.e. a KV cache covering the first
L tokens of a document — is planned with the paper's machinery: Dijkstra
over segment descriptors (directed/monoid case), cached segments vs.
prefill cost from a monotone cost model.  Gaps are prefilled in fixed-size
chunks (the paper's ``l``) and each chunk is materialized for future
requests — Alg 2, with KV segments in place of logistic-regression chunk
models.

Two front-ends share the machinery here:

  * :class:`ServeEngine` — one session over one document (the original
    single-tenant API, kept intact);
  * :class:`repro.serve.session.SessionManager` — N sessions over a shared
    document-keyed :class:`SegmentStore` with continuously-batched decode.

Both drive a :class:`PrefixCacheBuilder`, which owns the jitted model entry
points so compiled executables are shared across every session.
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.cost import CostModel, serve_cost_model  # noqa: F401  (re-export:
# serve_cost_model moved to core.cost so the analytical planner and the
# serving layer share one F/C vocabulary; importing it from here keeps
# existing callers working)
from repro.core.descriptors import Range
from repro.core.optimizer import Plan, baseline_plan, shortest_plan
from repro.kernels.common import bucket_len

from .kv_cache import (DEFAULT_DOC, SegmentStore, cache_len, chunk_segment,
                       insert_cache, pad_cache_to, slice_cache)

#: Stored chunks one grouped prompt-build pass covers.  A pass streams
#: every weight once whatever its length: deepseek-67b-4L's 3.608e9 bf16
#: parameters (4 layers of 692.2M, the 838.9M head) are 7.22 GB, 8.81 ms at
#: a v5e's 819 GB/s, against 5.54 GFLOP of matmuls a token at 197 TFLOP/s.
#: A pass stops waiting on HBM past 8.81 ms × 197e12 / 5.54e9 ≈ 313 tokens,
#: so 4 chunks of 128 (512 tokens) reach past that ridge.
GROUP_CHUNKS = 4


@dataclass
class ServeStats:
    requests: int = 0
    tokens_reused: int = 0
    tokens_computed: int = 0
    tokens_decoded: int = 0
    #: weight passes over full stored chunks in prompt builds, and the
    #: tokens they computed: ``GROUP_CHUNKS`` chunks a pass where the model
    #: groups them, one where it does not
    extend_passes: int = 0
    extend_pass_tokens: int = 0
    planner_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0

    # every derived rate below degrades to 0.0 (never NaN/inf) on
    # zero-traffic runs, so an idle server's report stays printable
    @property
    def reuse_frac(self) -> float:
        tot = self.tokens_reused + self.tokens_computed
        return self.tokens_reused / tot if tot else 0.0

    @property
    def tokens_per_extend_pass(self) -> float:
        return (self.extend_pass_tokens / self.extend_passes
                if self.extend_passes else 0.0)

    @property
    def prefill_tok_s(self) -> float:
        done = self.tokens_reused + self.tokens_computed
        return done / self.prefill_s if self.prefill_s > 0 else 0.0

    @property
    def decode_tok_s(self) -> float:
        return (self.tokens_decoded / self.decode_s
                if self.decode_s > 0 else 0.0)


@dataclass
class PendingBuild:
    """Deferred store side-effects of one dispatched prefix build.

    The plan/dispatch/finalize split behind async prefill: ``build_prefix``
    with ``defer=True`` launches every gap's device dispatch but records
    the chunk materializations here instead of inserting them, and pins
    the plan's reuse segments (``pin_token``) so eviction cannot reclaim
    an entry the in-flight computation still reads.
    :meth:`PrefixCacheBuilder.finalize_build` lands the insertions — in
    the exact order the synchronous path would have — and releases the
    pins.  Flushing is host-cheap and non-blocking: the recorded trees are
    lazy jax arrays and the store's byte accounting is shape-metadata only.
    """
    doc_id: str
    requester: Optional[int]
    #: [(rng, bucket-padded cache tree)] in ascending document order
    puts: list = field(default_factory=list)
    pin_token: tuple = ()
    finalized: bool = False


class PrefixCacheBuilder:
    """Plans and assembles KV prefix caches against a (shared) SegmentStore.

    Stateless with respect to sessions: every call names the document
    (``doc_id`` keys the store's descriptor index) and the stats object to
    charge, so one builder serves any number of tenants with one set of
    compiled executables.

    Bucketed-cache invariants (PR 2) every entry point preserves:

      * caches returned by :meth:`build_prefix` / :meth:`prefix_with_logits`
        ride at capacity ``bucket_len(max(length, capacity), seq_bucket)``
        along the sequence axis — the same token buckets batched decode
        packs to, so a fresh prefix drops into a decode pack without a
        reshape;
      * ``start`` / valid length is a **traced** int32 operand of the
        extend paths, so one XLA executable per (cache bucket, chunk
        shape) serves every chunk of every request; positions beyond the
        valid length hold garbage that causal masking excludes;
      * ``lowerings`` counts actual jit traces per entry point (the
        wrapper body only runs while tracing), which is what
        ``tests/test_prefill_recompile.py`` pins down: cold prefill cost
        is O(#buckets) executables, not O(#chunks) — and, with the store
        holding bucket-padded segments (PR 4), the *reuse* path's
        ``insert`` executables are O(#bucket pairs), not O(#distinct
        segment lengths).

    Cost-model hooks (PR 3): ``self.cost`` is the *unified*
    :class:`~repro.core.cost.CostModel` (serving calibration via
    :func:`~repro.core.cost.serve_cost_model`) and should be the same
    instance the SegmentStore evicts with — planner edge weights,
    decode-segment admission (``cost.admit``), and eviction victim
    scores then price fetch/rebuild/load identically.
    """

    def __init__(self, model, params, store: SegmentStore, *,
                 chunk_tokens: int = 64,
                 seq_bucket: int = 64,
                 cost_model: Optional[CostModel] = None) -> None:
        self.model = model
        self.params = params
        self.store = store
        self.chunk = chunk_tokens
        self.seq_bucket = seq_bucket
        self.cost = cost_model if cost_model is not None else serve_cost_model()
        # every entry point is shape-stable: caches ride at a bucketed
        # capacity and `start` is a traced operand, so the executables
        # below are compiled O(#buckets) times, not O(#chunks)
        self.lowerings = {"prefill": 0, "extend": 0, "extend_many": 0,
                          "extend_group": 0, "insert": 0}
        #: chunks per gap-filling weight pass (``_fill_gap``)
        self.group_chunks = GROUP_CHUNKS if model.extend_groupable() else 1
        #: segments dequantized on the reuse path (int8 residents whose
        #: payload was reconstructed before entering the jitted insert)
        self.dequants = 0
        #: reuse steps served from a cross-shard fetch (the sharded
        #: store marks transient fetched segments; a plain store never
        #: sets the flag, so this stays 0 off the sharded path)
        self.fetched_segments = 0
        self._jit_prefill = jax.jit(self._counted(model.prefill, "prefill"))
        self._jit_extend = jax.jit(self._counted(model.prefill_extend, "extend"))
        self._jit_extend_many = jax.jit(
            self._counted(model.prefill_extend_many, "extend_many"))
        self._jit_extend_group = jax.jit(
            self._counted(model.prefill_extend_many, "extend_group"))
        self._jit_insert = jax.jit(self._counted(insert_cache, "insert"))

    def _segment_caches(self, seg):
        """A reuse segment's caches at model precision.

        int8 residents reconstruct through the fused dequant kernel
        (``kernels/quant_kv``; blocked jnp reference off-TPU) before the
        jitted ``insert_cache`` consumes them — ``insert_cache`` casts
        the segment to the destination dtype, so feeding it raw int8
        codes would silently insert garbage magnitudes.  The store copy
        stays quantized; only this plan's working cache pays fp32 bytes.
        """
        if getattr(seg, "fetched", False):
            self.fetched_segments += 1
        if seg.precision != "int8" or seg.quant is None:
            return seg.caches
        from repro.core.quant import dequantize_tree

        self.dequants += 1
        return dequantize_tree(seg.caches, seg.quant)

    def _counted(self, fn, key: str):
        """Wrap ``fn`` so each jit trace (= one XLA lowering) is counted.

        The wrapper body only runs while jax traces a new input signature,
        so the counter is exactly the number of distinct executables —
        what the recompile-count regression test pins down.
        """
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            self.lowerings[key] += 1
            return fn(*args, **kwargs)
        return wrapper

    @property
    def extend_lowerings(self) -> int:
        """Total prefill/extend executables compiled so far."""
        return sum(self.lowerings.values())

    # ------------------------------------------------------------------
    def plan_prefix(self, length: int, *, doc_id: str = DEFAULT_DOC,
                    stats: Optional[ServeStats] = None) -> Plan:
        t0 = time.perf_counter()
        plan = shortest_plan(
            self.store.index(doc_id), Range(0, length), self.cost,
            self.store.segment_bytes(doc_id), directed=True,
        )
        if stats is not None:
            stats.planner_s += time.perf_counter() - t0
        return plan

    def build_prefix(self, doc: np.ndarray, length: int, *,
                     doc_id: str = DEFAULT_DOC,
                     extras: Optional[dict] = None,
                     stats: Optional[ServeStats] = None,
                     materialize: bool = True,
                     requester: Optional[int] = None,
                     capacity: Optional[int] = None,
                     defer: bool = False):
        """Assemble the KV cache for document[:length] via the cheapest plan.

        Returns (caches, plan) with the caches' sequence axis padded to
        ``bucket_len(max(length, capacity), seq_bucket)`` — the shape
        discipline that bounds compilation: every gap is filled through
        the shape-stable ``prefill_extend`` entry points at this bucketed
        capacity, and a whole gap's worth of full chunks goes through at
        most two ``prefill_extend_many`` dispatches (jitted fori_loops over
        slots of grouped and of single chunks) instead of one host
        round-trip per chunk.  Each chunk is still materialized for
        future requests (paper Alg 2 behaviour).
        Segments the plan references are pinned for the duration so chunk
        puts can never evict them mid-execution.

        With ``defer=True`` this is the *dispatch phase* of the pipeline:
        all device work is launched (asynchronously — nothing here blocks
        on it), but chunk materializations are recorded on the returned
        :class:`PendingBuild` instead of hitting the store, and the plan's
        reuse segments stay pinned under its ``pin_token``.  The caller
        owns the finalize phase (:meth:`finalize_build`), which must run
        before any *other* store insertion so segment ids, admission, and
        eviction decisions replay exactly as in the synchronous path.
        Returns ``(caches, plan, pending)``.
        """
        stats = stats if stats is not None else ServeStats()
        extras = extras or {}
        plan = self.plan_prefix(length, doc_id=doc_id, stats=stats)
        steps = sorted(plan.steps, key=lambda s: s.rng.lo)  # DAG path is ordered
        cap = bucket_len(max(length, capacity or 0), self.seq_bucket)
        # bucket-padded segments are inserted whole (their padded tail is
        # overwritten by the next step or causal-masked), so the cache
        # needs headroom for every reuse step's *capacity*, not just its
        # valid end — dynamic_update_slice clamps out-of-range starts,
        # which would silently corrupt prefix rows
        for st in steps:
            if st.model_id is not None:
                end = st.rng.lo + self.store.capacity(st.model_id)
                cap = max(cap, bucket_len(end, self.seq_bucket))
        pending = PendingBuild(doc_id=doc_id, requester=requester) \
            if defer else None
        if not materialize:
            sink = None
        elif defer:
            sink = lambda rng, seg: pending.puts.append((rng, seg))  # noqa: E731
        else:
            sink = lambda rng, seg: self.store.put(  # noqa: E731
                rng, seg, doc_id=doc_id, created_by=requester)
        if defer:
            pending.pin_token = self.store.pin(plan.models_used)
            ctx = contextlib.nullcontext()
        else:
            ctx = self.store.pinned(plan.models_used)
        caches = None
        t0 = time.perf_counter()
        try:
            with ctx:
                # start every reuse segment's tier promotion up front —
                # under the plan's pins, so promoted entries cannot be
                # reclaimed before their insert — letting disk reads and
                # h2d copies overlap the gap prefills below
                self.store.prefetch_ids(plan.models_used)
                for st in steps:
                    if st.model_id is not None:
                        seg = self.store.get(st.model_id, requester=requester)
                        seg_caches = self._segment_caches(seg)
                        if caches is None:
                            # plan anchor at 0: adopt the segment (incl. its
                            # state leaves) and grow to the request capacity
                            caches = pad_cache_to(seg_caches, cap)
                        else:
                            # shape-stable insert: one executable per (cache
                            # bucket, segment bucket) pair, not per valid length
                            caches = self._jit_insert(
                                caches, seg_caches, jnp.asarray(st.rng.lo, jnp.int32))
                        stats.tokens_reused += st.rng.size
                    else:
                        caches = self._fill_gap(doc, st.rng, caches, cap, extras,
                                                stats=stats, sink=sink)
        except BaseException:
            # the sync path's context manager releases pins on any failure;
            # the deferred path must match, or a crashed dispatch leaks its
            # plan's pins for the life of the store
            self.abandon_build(pending)
            raise
        if caches is not None:
            caches = pad_cache_to(caches, cap)
        stats.prefill_s += time.perf_counter() - t0
        if defer:
            return caches, plan, pending
        return caches, plan

    def abandon_build(self, pending: Optional[PendingBuild]) -> None:
        """Release a deferred build's pins without landing its insertions.

        The exception path of the dispatch phase: the recorded trees may
        reference a failed computation, so they are dropped rather than
        stored (the next request simply re-prefills those chunks), but the
        pins must never outlive the build.
        """
        if pending is None or pending.finalized:
            return
        pending.finalized = True
        pending.puts = []
        self.store.unpin(pending.pin_token)

    def finalize_build(self, pending: Optional[PendingBuild]) -> None:
        """Finalize phase of a deferred build: land the recorded chunk
        insertions in dispatch order and release the plan's pins.

        Host-cheap and non-blocking (the trees are lazy jax arrays; byte
        accounting is shape metadata), so the scheduler can flush pending
        builds without ever waiting on the device.  Idempotent: a build is
        finalized at most once.
        """
        if pending is None or pending.finalized:
            return
        pending.finalized = True
        for rng, seg in pending.puts:
            self.store.put(rng, seg, doc_id=pending.doc_id,
                           created_by=pending.requester)
        pending.puts = []
        self.store.unpin(pending.pin_token)

    def _fill_gap(self, doc, rng: Range, caches, cap: int, extras, *,
                  stats, sink):
        """Prefill one uncovered plan step [rng.lo, rng.hi) into ``caches``.

        Full chunks run in fused ``prefill_extend_many`` dispatches: first
        every whole group of ``group_chunks`` chunks, one weight pass a
        group, then the chunks left over, one pass each.  Groups are only
        ever full, so no pass writes past ``hi``.  At most one ragged
        remainder runs as a single ``prefill_extend``.
        Only a cold start at position 0 uses the exact-shape ``prefill``
        (one compile per distinct first-chunk length).  ``sink`` receives
        each chunk's materialized segment (None = don't materialize); the
        synchronous path inserts immediately, the deferred path records
        for finalize-time insertion.
        """
        lo, hi = rng.lo, rng.hi
        if caches is None and lo == 0:
            first = min(self.chunk, hi)
            batch = {"tokens": jnp.asarray(doc[None, :first]), **extras}
            _, caches = self._jit_prefill(self.params, batch)
            if sink is not None:
                sink(Range(0, first), slice_cache(caches, 0, first))
            stats.tokens_computed += first
            lo = first
            if lo >= hi:
                return caches
        caches = pad_cache_to(caches, cap)
        # dynamic_update_slice *clamps* an out-of-range start instead of
        # raising, which would silently overwrite prefix rows — check the
        # capacity contract eagerly (host ints, no jit impact).  cache_len
        # is 0 for pure-SSM caches (no sequence leaves): nothing to clamp.
        cur = cache_len(caches)
        assert cur == 0 or cur >= hi, f"cache capacity {cur} < gap end {hi}"
        n_full = (hi - lo) // self.chunk
        g = self.group_chunks
        n_grouped = n_full // g * g if g > 1 else 0
        if n_grouped:
            caches, _ = self._extend_passes(
                self._jit_extend_group, doc, lo, n_grouped,
                self.chunk * g, caches, cap)
        if n_full > n_grouped:
            caches, states = self._extend_passes(
                self._jit_extend_many, doc, lo + n_grouped * self.chunk,
                n_full - n_grouped, self.chunk, caches, cap)
        if n_full:
            if sink is not None:
                # stored segments stay one chunk each; grouped stacks keep
                # no running state, so their chunks are plain slices
                for i in range(n_full):
                    a = lo + i * self.chunk
                    sink(Range(a, a + self.chunk),
                         slice_cache(caches, a, a + self.chunk)
                         if i < n_grouped else
                         chunk_segment(caches, states, i - n_grouped, a,
                                       a + self.chunk))
            passes = n_grouped // g + n_full - n_grouped
            stats.extend_passes += passes
            stats.extend_pass_tokens += n_full * self.chunk
            trace.count("repro.serve.extend_pass", passes)
            trace.count("repro.serve.extend_pass_tokens", n_full * self.chunk)
            stats.tokens_computed += n_full * self.chunk
            lo += n_full * self.chunk
        if lo < hi:                              # ragged remainder chunk
            toks = jnp.asarray(doc[None, lo:hi])
            _, caches = self._jit_extend(self.params, caches, toks,
                                         jnp.asarray(lo, jnp.int32))
            if sink is not None:
                sink(Range(lo, hi), slice_cache(caches, lo, hi))
            stats.tokens_computed += hi - lo
        return caches

    def _extend_passes(self, jitted, doc, lo: int, n_chunks: int,
                       width: int, caches, cap: int):
        """Extend ``caches`` with ``doc[lo : lo + n_chunks·chunk]`` in one
        dispatch of passes ``width`` tokens long (a chunk multiple).  The
        token buffer has a slot for every pass ``cap`` could hold, so the
        executable depends only on (cap, width).  Returns (caches, the
        per-pass state snapshots)."""
        n = n_chunks * self.chunk // width
        toks = np.zeros((1, cap // width, width), np.int32)
        toks[0, :n] = np.asarray(doc[lo:lo + n * width]).reshape(n, width)
        _, caches, states = jitted(
            self.params, caches, jnp.asarray(toks),
            jnp.asarray(lo, jnp.int32), jnp.asarray(n, jnp.int32))
        return caches, states

    def prefix_with_logits(self, doc: np.ndarray, prefix_len: int, *,
                           doc_id: str = DEFAULT_DOC,
                           extras: Optional[dict] = None,
                           stats: Optional[ServeStats] = None,
                           requester: Optional[int] = None,
                           capacity: Optional[int] = None,
                           defer: bool = False):
        """Cache for [0, prefix_len) plus the logits of its last position.

        The last prefix token runs through a 1-token extend so its logits
        (= the first sampling distribution) come out of the same pass that
        completes the cache — correct for running-state (SSD) layers too.
        Pass ``capacity`` (e.g. prefix_len + n_new) so the returned caches
        are already padded to the decode bucket the request will need.

        ``defer=True`` returns ``(logits, caches, plan, pending)`` — the
        dispatch phase of an async prefill ticket (see
        :meth:`build_prefix`): everything is launched, nothing is awaited,
        and the store insertions wait on :meth:`finalize_build`.
        """
        stats = stats if stats is not None else ServeStats()
        extras = extras or {}
        if prefix_len < 2:
            batch = {"tokens": jnp.asarray(doc[None, :prefix_len]), **extras}
            t0 = time.perf_counter()
            logits, caches = self._jit_prefill(self.params, batch)
            stats.prefill_s += time.perf_counter() - t0
            stats.tokens_computed += prefix_len
            plan = baseline_plan(Range(0, prefix_len), self.cost)
            if defer:   # nothing to insert or pin; empty finalize for symmetry
                return logits, caches, plan, PendingBuild(
                    doc_id=doc_id, requester=requester)
            return logits, caches, plan
        built = self.build_prefix(
            doc, prefix_len - 1, doc_id=doc_id, extras=extras, stats=stats,
            materialize=True, requester=requester,
            capacity=max(prefix_len, capacity or 0), defer=defer)
        caches, plan = built[0], built[1]
        try:
            toks = jnp.asarray(doc[None, prefix_len - 1: prefix_len])
            cur = cache_len(caches)
            assert cur == 0 or cur >= prefix_len, (
                f"cache capacity {cur} < prefix {prefix_len}")
            t0 = time.perf_counter()
            logits, caches = self._jit_extend(
                self.params, caches, toks,
                jnp.asarray(prefix_len - 1, jnp.int32))
        except BaseException:
            if defer:       # a failed boundary extend must not leak pins
                self.abandon_build(built[2])
            raise
        stats.prefill_s += time.perf_counter() - t0
        stats.tokens_computed += 1
        if defer:
            return logits, caches, plan, built[2]
        return logits, caches, plan

    def prefill_raw(self, batch):
        """Jitted from-scratch prefill (no planning, no materialization)."""
        return self._jit_prefill(self.params, batch)


class ServeEngine:
    """Single-session serving over one document (original API).

    ``store``/``doc_id`` default to a private store; pass a shared
    :class:`SegmentStore` and a stable ``doc_id`` to join a multi-tenant
    deployment (see :class:`repro.serve.session.SessionManager`).
    """

    def __init__(
        self,
        model,
        params,
        doc_tokens: np.ndarray,
        *,
        extras: Optional[dict] = None,
        chunk_tokens: int = 64,
        seq_bucket: int = 64,
        cost_model: Optional[CostModel] = None,
        byte_budget: Optional[int] = None,
        store: Optional[SegmentStore] = None,
        doc_id: str = DEFAULT_DOC,
        eviction_policy: Optional[str] = None,
    ) -> None:
        self.model = model
        self.params = params
        self.doc = np.asarray(doc_tokens, np.int32)
        self.extras = extras or {}
        self.doc_id = doc_id
        if store is not None and byte_budget is not None:
            raise ValueError(
                "pass byte_budget only when the engine owns its store; a "
                "shared store's budget is set where the store is created")
        if store is not None and eviction_policy is not None:
            raise ValueError(
                "pass eviction_policy only when the engine owns its store; "
                "a shared store's policy is set where the store is created")
        cost_model = cost_model if cost_model is not None else serve_cost_model()
        if store is None:
            # the engine-owned store evicts with the same cost model the
            # planner prices plans with (one F/C vocabulary end to end),
            # and buckets stored segments at the builder's seq granularity
            # so warm hits reuse the builder's compiled insert executables
            store = SegmentStore(byte_budget=byte_budget,
                                 cost_model=cost_model,
                                 policy=eviction_policy,
                                 seq_bucket=seq_bucket)
        self.store = store
        self.builder = PrefixCacheBuilder(model, params, self.store,
                                          chunk_tokens=chunk_tokens,
                                          seq_bucket=seq_bucket,
                                          cost_model=cost_model)
        self.cost = self.builder.cost
        self.stats = ServeStats()
        self._jit_decode = jax.jit(model.decode_step)

    @property
    def chunk(self) -> int:
        return self.builder.chunk

    # ------------------------------------------------------------------
    def plan_prefix(self, length: int) -> Plan:
        return self.builder.plan_prefix(length, doc_id=self.doc_id,
                                        stats=self.stats)

    def build_prefix(self, length: int, *, materialize: bool = True):
        return self.builder.build_prefix(
            self.doc, length, doc_id=self.doc_id, extras=self.extras,
            stats=self.stats, materialize=materialize)

    # ------------------------------------------------------------------
    def generate(self, prefix_len: int, n_new: int, *, greedy: bool = True,
                 seed: int = 0):
        """Serve one request: cache for [0, prefix_len), then decode n_new."""
        self.stats.requests += 1
        logits, caches, plan = self.builder.prefix_with_logits(
            self.doc, prefix_len, doc_id=self.doc_id, extras=self.extras,
            stats=self.stats, capacity=prefix_len + n_new)
        # prefix construction already padded to a bucket covering the decode
        # window; this is a no-op except on the short-prefix prefill path
        caches = pad_cache_to(
            caches, bucket_len(prefix_len + n_new, self.builder.seq_bucket))
        t0 = time.perf_counter()
        out_tokens = []
        key = jax.random.PRNGKey(seed)
        pos = jnp.asarray([prefix_len], jnp.int32)
        for i in range(n_new):
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits).astype(jnp.int32)
            out_tokens.append(int(nxt[0]))
            if i < n_new - 1:  # the last token's logits are never consumed
                logits, caches = self._jit_decode(self.params, caches, nxt[:, None], pos)
                pos = pos + 1
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.tokens_decoded += len(out_tokens)
        return out_tokens, plan

    # ------------------------------------------------------------------
    def update_document(self, new_tokens: np.ndarray):
        """Swap in edited document content, keeping the reusable KV prefix.

        Single-session counterpart of
        :meth:`repro.serve.session.SessionManager.update_document`: diff
        old vs new tokens, rekey every stored segment strictly before the
        divergence point to the edited content's key when the cost model
        prices the edit-rebuild below from-scratch, and release the rest
        from every tier.  Returns the :class:`~repro.core.planner.EditPlan`.
        """
        from repro.core.planner import plan_edit

        from .session import doc_key

        new_doc = np.asarray(new_tokens, np.int32)
        old_id = self.doc_id
        new_id = doc_key(new_doc, self.extras)
        eplan = plan_edit(self.doc, new_doc, self.store.index(old_id),
                          self.cost, self.store.segment_bytes(old_id))
        if new_id != old_id:
            if eplan.action == "edit":
                self.store.rekey(old_id, new_id, upto=eplan.divergence)
            self.store.release_doc(old_id)
        self.doc, self.doc_id = new_doc, new_id
        return eplan

    def baseline_build(self, length: int):
        """No-reuse reference: prefill everything from scratch."""
        batch = {"tokens": jnp.asarray(self.doc[None, :length]), **self.extras}
        t0 = time.perf_counter()
        logits, caches = self.builder.prefill_raw(batch)
        jax.block_until_ready(logits)
        return caches, time.perf_counter() - t0
