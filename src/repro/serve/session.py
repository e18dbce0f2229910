"""Multi-session batched serving over a shared segment store.

The ROADMAP's "heavy traffic" direction applied to the paper's machinery: a
:class:`SessionManager` owns N active documents (tenants).  Each request's
prefix is planned with the directed Dijkstra against the **shared**,
document-keyed :class:`SegmentStore` — sessions over the same document hit
each other's materialized segments (the compounding reuse F-IVM/LINVIEW
observe for shared views), sessions over different documents stay isolated
by construction (per-document descriptor indexes), and one global LRU byte
budget arbitrates storage across all tenants.

Decode is continuously batched: every scheduler step coalesces the ready
sessions into one ``decode_step`` call, padding each cache to a shared
bucketed capacity (``kernels.common.bucket_len``) and concatenating along
the batch axis.  Per-row positions + the decode paths' position masks make
ragged progress exact — a padded row attends only to its own ``pos``
prefix, so batched outputs are bit-identical to single-session decode.

Decode-time segment materialization (PR 3): the tokens a request emits
*extend the document* — decode already wrote their KV into the session's
cache, so when the request drains, that slice is written back into the
shared store under the content key of the generated continuation
(``doc[:prefix] + generated``), gated by the unified cost model's
admission check (``CostModel.admit``: expected reuse benefit must exceed
the segment's byte cost).  The base document's prefix segments are
*aliased* into the continuation's descriptor index rather than copied, so
a follow-up request over generated context plans entirely from the store
— no re-prefill of text the server itself produced.

Pipelined serving (PR 5): the loop is an explicit three-stage pipeline —
**admit → prefill → decode**.  ``submit`` (admit) plans the prefix and
*launches* the build (async ``prefill_extend_many`` dispatches per plan
gap — JAX async dispatch means nothing blocks the host), parking the
session behind a :class:`PrefillTicket`.  The scheduler keeps batching
already-warm sessions while tickets are in flight; a ticketed session
*joins* the decode lanes only when its build's result is ready (polled
without blocking), or when nothing else can decode.  Store insertions of
the build's chunk segments are deferred to ticket-finalize time and land
in submit order, and the plan's reuse segments stay pinned until then —
so token streams *and* store contents are bit-identical to the
synchronous loop (``async_prefill=False`` /
``REPRO_ASYNC_PREFILL=0``), which stalls every decoder for the full
build instead.
"""
from __future__ import annotations

import hashlib
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import CostModel, serve_cost_model
from repro.core.descriptors import Range
from repro.core.optimizer import Plan
from repro.kernels.common import (bucket_len, decode_kernel_mode,
                                  extend_kernel_mode)

from .engine import PendingBuild, PrefixCacheBuilder, ServeStats
from .kv_cache import (SEQ_KEYS, SegmentStore, _leaf_key, cache_len,
                       cache_nbytes, pad_cache_to, slice_cache)


def doc_key(doc_tokens: np.ndarray, extras: Optional[dict] = None) -> str:
    """Content-derived document id: identical documents share segments.

    ``extras`` (encoder features / image embeddings) condition the KV a
    prefill produces — cross-attention constants are baked into cached
    segments — so they are part of document identity: same tokens with
    different extras must NOT share segments.

    sha256 (like every content key): the sharded store's consistent-hash
    ring places documents by this id, so it must be identical across
    processes and hosts regardless of ``PYTHONHASHSEED``.
    """
    h = hashlib.sha256(np.ascontiguousarray(doc_tokens, np.int32).tobytes())
    for k in sorted(extras or {}):
        h.update(k.encode())
        h.update(np.ascontiguousarray(extras[k]).tobytes())
    return h.hexdigest()[:12]


def batch_caches(caches_list: list, *, owned: bool = False) -> Any:
    """Concatenate per-session caches ((L, 1, ...) leaves) along batch.

    With ``owned=True`` the pack is guaranteed to own its buffers — the
    donation-safe handoff at the session→decode boundary.
    ``jnp.concatenate`` of a single operand returns it unchanged, and a
    session cache can itself alias a store-resident segment (a no-op pad
    at the plan anchor), so a 1-row pack must copy when the batched
    decode jit donates its cache operand: donating an aliased buffer
    would invalidate store bytes under every other session's feet.
    Callers whose decode never donates (the CPU backend) skip the copy —
    without donation an aliased immutable buffer is harmless.
    """
    if len(caches_list) == 1:
        if owned:
            return jax.tree.map(jnp.copy, caches_list[0])
        return caches_list[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches_list)


def split_caches(caches, n: int) -> list:
    """Inverse of :func:`batch_caches`: per-row views of a batched cache."""
    return [jax.tree.map(lambda x: x[:, i:i + 1], caches) for i in range(n)]


def batch_signature(caches) -> tuple:
    """Shape key under which caches can be batched together.

    Batch (axis 1) and the SEQ leaves' sequence axis (axis 2) are
    normalized away — those are what padding/concat adjust; everything else
    (tree structure, layer counts, head dims, context lengths, dtypes) must
    match exactly.
    """
    leaves = jax.tree_util.tree_leaves_with_path(caches)
    treedef = jax.tree_util.tree_structure(caches)
    sig = []
    for path, x in leaves:
        key = _leaf_key(path)
        shape = list(x.shape)
        shape[1] = -1
        if key in SEQ_KEYS:
            shape[2] = -1
        sig.append((key, tuple(shape), str(x.dtype)))
    return (treedef, tuple(sig))


@dataclass
class PrefillTicket:
    """One async prefix build in flight between submit and first decode.

    The pipeline's prefill-stage token: ``submit`` creates it after
    launching the build's device dispatches, the scheduler polls
    :meth:`ready` (non-blocking) each round, and the owning session enters
    the decode lanes only after :meth:`SessionManager._join_ticket`.  Two
    independent completions hang off it:

      * **store finalize** — ``pending`` holds the build's deferred chunk
        insertions plus the pin token protecting the plan's reuse
        segments; flushed FIFO (submit order) by the manager so store
        contents replay the synchronous loop exactly;
      * **compute join** — the first decode of this session consumes the
        build's logits/caches, so the join blocks on them (a no-op when
        the poll already reported ready) and the wait is attributed to
        the ticket, not to the warm sessions' decode time.
    """
    sid: int
    seq: int                    # FIFO order (= launch index)
    plan: Plan
    pending: PendingBuild
    logits: Any                 # build result the first decode consumes
    submitted_s: float
    joined: bool = False
    join_wait_s: float = 0.0

    def ready(self) -> bool:
        """Has the dispatched build completed on device?  Never blocks."""
        try:
            return bool(self.logits.is_ready())
        except AttributeError:      # non-jax logits (already concrete)
            return True


@dataclass
class Session:
    sid: int
    doc_id: str
    doc: np.ndarray
    extras: dict = field(default_factory=dict)
    stats: ServeStats = field(default_factory=ServeStats)
    # in-flight request state
    caches: Any = None
    logits: Any = None          # (1, V) distribution for the next token
    pos: int = 0                # next decode position
    capacity: int = 0           # required KV capacity (prefix + n_new)
    req_prefix: int = 0         # prefix length of the in-flight request
    mat_pending: bool = False   # drained request's KV awaits write-back
    fork_owned: bool = False    # doc_id is a generated fork this session made
    remaining: int = 0
    greedy: bool = True
    key: Any = None
    next_tok: int = -1
    greedy_next: Optional[int] = None  # batched-argmax result from last decode
    ticket: Optional[PrefillTicket] = None  # un-joined async prefix build
    out_tokens: list = field(default_factory=list)
    plans: list = field(default_factory=list)

    @property
    def busy(self) -> bool:
        return self.remaining > 0


@dataclass
class SchedulerStats:
    decode_calls: int = 0
    decode_rows: int = 0
    pack_rebuilds: int = 0
    decode_segments: int = 0    # decode-KV segments admitted to the store
    decode_rejects: int = 0     # ... rejected by the cost-model admission
    # pipeline (async-prefill) counters
    tickets_launched: int = 0   # async prefix builds dispatched
    tickets_joined: int = 0     # ... whose sessions entered decode
    join_wait_s: float = 0.0    # host time blocked waiting on builds at join
    overlap_steps: int = 0      # decode rounds run while ≥1 build in flight
    overlap_rows: int = 0       # decode rows produced in those rounds
    # delta-update (document edit) counters
    edits: int = 0              # update_document calls applied
    edit_reused_segments: int = 0  # segments rekeyed to the edited content
    edit_orphaned: int = 0      # segments invalidated (released) by edits
    edit_cancelled: int = 0     # in-flight requests superseded by an edit
    # ragged-decode observability
    decode_valid_tokens: int = 0   # Σ per-row live KV (pos+1) over decode calls
    decode_padded_tokens: int = 0  # Σ rows × padded pack capacity
    decode_attn_flops: float = 0.0  # estimated attention FLOPs actually executed

    # all derived means guard the zero-traffic case: an idle server's
    # report prints 0.0, never NaN
    @property
    def mean_batch(self) -> float:
        return self.decode_rows / self.decode_calls if self.decode_calls else 0.0

    @property
    def decode_padded_frac(self) -> float:
        """Valid tokens ÷ padded pack capacity (1.0 = zero padding waste)."""
        return (self.decode_valid_tokens / self.decode_padded_tokens
                if self.decode_padded_tokens else 0.0)

    @property
    def overlap_batch(self) -> float:
        return (self.overlap_rows / self.overlap_steps
                if self.overlap_steps else 0.0)

    @property
    def mean_join_wait_s(self) -> float:
        return (self.join_wait_s / self.tickets_joined
                if self.tickets_joined else 0.0)


class SessionManager:
    """N concurrent serving sessions over one model + one shared store."""

    def __init__(self, model, params, *,
                 chunk_tokens: int = 64,
                 cost_model: Optional[CostModel] = None,
                 byte_budget: Optional[int] = None,
                 decode_bucket: int = 64,
                 max_batch: int = 8,
                 eviction_policy: Optional[str] = None,
                 decode_materialize: Optional[bool] = None,
                 async_prefill: Optional[bool] = None,
                 merge_decode_packs: Optional[bool] = None,
                 store: Optional[SegmentStore] = None) -> None:
        self.model = model
        self.params = params
        if store is not None and byte_budget is not None:
            raise ValueError(
                "pass byte_budget only when the manager owns its store; a "
                "shared/reloaded store's budget is set where it is created")
        if store is not None and eviction_policy is not None:
            raise ValueError(
                "pass eviction_policy only when the manager owns its store; "
                "a shared/reloaded store's policy is set where it is created")
        if store is not None and cost_model is not None \
                and cost_model is not store.cost:
            # overwriting an adopted store's cost model would silently
            # reprice admission/eviction for every other manager sharing
            # it — same contract as byte_budget/eviction_policy above
            raise ValueError(
                "pass cost_model only when the manager owns its store (or "
                "pass the store's own cost model); a shared/reloaded "
                "store's pricing is set where the store is created")
        # one cost model prices everything: planner edges, decode-segment
        # admission, and the store's eviction victim scores.  When an
        # existing store is adopted (warm restart / shared deployment),
        # inherit the store's so they cannot disagree.
        if store is not None:
            self.cost = store.cost
        else:
            self.cost = cost_model if cost_model is not None else serve_cost_model()
            store = SegmentStore(byte_budget=byte_budget,
                                 cost_model=self.cost,
                                 policy=eviction_policy,
                                 seq_bucket=decode_bucket)
        self.store = store
        # prefill pads caches to the same token buckets batched decode uses,
        # so a freshly built prefix drops into a decode pack without a
        # reshape and prefill executables are shared across requests
        self.builder = PrefixCacheBuilder(model, params, self.store,
                                          chunk_tokens=chunk_tokens,
                                          seq_bucket=decode_bucket,
                                          cost_model=self.cost)
        if decode_materialize is None:
            decode_materialize = os.environ.get(
                "REPRO_DECODE_MATERIALIZE", "1") != "0"
        self.decode_materialize = decode_materialize
        # admit → prefill → decode pipeline (default): submit launches the
        # build and the scheduler joins it before the session's first
        # decode; REPRO_ASYNC_PREFILL=0 / async_prefill=False restores the
        # stall-on-submit loop (identical tokens and store contents)
        if async_prefill is None:
            async_prefill = os.environ.get("REPRO_ASYNC_PREFILL", "1") != "0"
        self.async_prefill = async_prefill
        self.decode_bucket = decode_bucket
        self.max_batch = max_batch
        # merged ragged packs: with a decode path whose per-row output is
        # bit-invariant to padded capacity (kernel/blocked — masked tail
        # contributions are exact zeros), mixed-capacity sessions can share
        # one pack padded to the max bucket: bigger batches per decode
        # call, and the ragged early-exit makes the padding ~free.  The
        # legacy dense path reads the full capacity per row, so there the
        # pre-kernel capacity-split grouping remains the default
        # (REPRO_DECODE_KERNEL=0 ⇒ behavior bit-identical to pre-kernel).
        self.decode_mode = decode_kernel_mode()
        # how prefix builds run their suffix attention ('kernel' | 'jax');
        # like decode_mode, read once here for the report
        self.extend_mode = extend_kernel_mode()
        if merge_decode_packs is None:
            merge_decode_packs = self.decode_mode != "dense"
        self.merge_decode_packs = merge_decode_packs
        # attention-bearing layers, for the decode-FLOP estimate
        self._n_attn_layers = sum(
            n * sum(1 for spec in period if spec.mixer in ("attn", "mla"))
            for period, n in model.segments)
        # per-request counters live on each Session (folded into
        # _closed_stats on close); the manager-level object only carries the
        # shared batched-decode wall time.  aggregate_stats() is the
        # authoritative combined view.
        self.stats = ServeStats()
        self.sched = SchedulerStats()
        self._closed_stats = ServeStats()
        self.sessions: dict[int, Session] = {}
        self._next_sid = 0
        # the decode jit donates its cache operand — in-place KV updates
        # instead of a full cache copy per step — so pack building forces
        # owned buffers (see batch_caches): a donated pack must never
        # alias a session's retained cache rows.  Donation holds on CPU
        # too, and the ragged ``row_caps`` fast path leans on it: its
        # per-row scatter writes only stay O(B) per step when XLA can
        # update the carried cache buffers in place.  ``row_caps`` is
        # static pack metadata (per-row KV capacities), so it sits in the
        # compile key, not in the traced operands.
        self._donate_decode = True
        self._jit_decode = jax.jit(
            model.decode_step,
            donate_argnums=(1,),
            static_argnames=("row_caps",))
        # live decode packs: tuple(sids) -> batched caches (padded to a bucket)
        self._packs: dict[tuple[int, ...], Any] = {}
        # un-finalized async builds, FIFO in submit order
        self._tickets: list[PrefillTicket] = []

    # -- session lifecycle -------------------------------------------------
    def add_session(self, doc_tokens: np.ndarray, *,
                    doc_id: Optional[str] = None,
                    extras: Optional[dict] = None) -> int:
        doc = np.asarray(doc_tokens, np.int32)
        sid = self._next_sid
        self._next_sid += 1
        self.sessions[sid] = Session(
            sid=sid, doc_id=doc_id if doc_id is not None else doc_key(doc, extras),
            doc=doc, extras=extras or {})
        return sid

    def close_session(self, sid: int) -> None:
        # land any deferred builds first: the closing session's own chunk
        # segments (and everyone else's) must reach the store in submit
        # order even if it never decoded a token
        self._flush_tickets()
        self._flush_packs([g for g in self._packs if sid in g])
        s = self.sessions.pop(sid, None)
        if s is not None:
            s.ticket = None
            if s.mat_pending:
                # the last request's generated KV outlives the session —
                # another tenant may continue the same generated document
                self._materialize_decode(s)
            # fold the session's counters into the closed-session totals so
            # aggregate_stats stays consistent after churn
            _accumulate(self._closed_stats, s.stats)

    # -- request admission (pipeline stage 1) ------------------------------
    def submit(self, sid: int, prefix_len: int, n_new: int, *,
               greedy: bool = True, seed: int = 0) -> Plan:
        """Admit one request: plan the prefix and launch its build.

        Async mode (default) dispatches the build and returns immediately
        with the plan — the session rides a :class:`PrefillTicket` until
        the scheduler joins it before its first decode, and the decode
        lanes keep running in the meantime.  Sync mode blocks here until
        the build completes (the pre-pipeline loop, kept as the bitwise
        reference and for `--sync-prefill` benchmarking).
        """
        s = self.sessions[sid]
        if s.busy:
            raise RuntimeError(f"session {sid} still has {s.remaining} tokens pending")
        # outstanding builds finalize before this one plans: their chunk
        # segments are what makes this plan see the same store state the
        # synchronous loop would have (and their puts must precede ours)
        self._flush_tickets()
        # a drained session's last pack can survive in _packs under the same
        # group tuple (e.g. it was the only decoder); flush any pack holding
        # this session so stale batched caches are never reused, while
        # unrelated in-flight packs stay intact
        self._flush_packs([g for g in self._packs if sid in g])
        if s.mat_pending:
            # last chance to write the previous request's generated KV back
            # before prefix_with_logits replaces the session caches
            self._materialize_decode(s)
        # prior-driven prefetch: start promoting this document's demoted
        # segments (host/disk -> device) before the plan is computed, so
        # tier reads overlap planning and build dispatch; documents whose
        # observed traffic never returns are skipped (prefetch_min_prior)
        self.store.prefetch(s.doc_id, upto=prefix_len)
        if self.async_prefill:
            logits, caches, plan, pending = self.builder.prefix_with_logits(
                s.doc, prefix_len, doc_id=s.doc_id, extras=s.extras,
                stats=s.stats, requester=sid, capacity=prefix_len + n_new,
                defer=True)
            self.sched.tickets_launched += 1
            s.ticket = PrefillTicket(
                sid=sid, seq=self.sched.tickets_launched, plan=plan,
                pending=pending, logits=logits,
                submitted_s=time.perf_counter())
            self._tickets.append(s.ticket)
        else:
            logits, caches, plan = self.builder.prefix_with_logits(
                s.doc, prefix_len, doc_id=s.doc_id, extras=s.extras,
                stats=s.stats, requester=sid, capacity=prefix_len + n_new)
            # the monolithic loop: every decoding session stalls until this
            # build has fully materialized on device
            t0 = time.perf_counter()
            jax.block_until_ready(logits)
            s.stats.prefill_s += time.perf_counter() - t0
        s.caches = caches
        s.logits = logits
        s.greedy_next = None
        s.pos = prefix_len
        s.capacity = prefix_len + n_new
        s.req_prefix = prefix_len
        s.remaining = n_new
        s.greedy = greedy
        s.key = jax.random.PRNGKey(seed)
        s.out_tokens = []
        s.plans.append(plan)
        s.stats.requests += 1
        return plan

    def submit_many(self, reqs, *, greedy: bool = True) -> list[Plan]:
        """Admit one scheduler tick's worth of requests together.

        ``reqs`` is ``[(sid, prefix_len, n_new, seed), ...]``.  Against a
        sharded store this is the cross-document coalescing point: every
        document's remote segments are resolved in **one** transport tick
        up front (at most one batched transfer per contacted shard), so
        the per-request prefetch inside :meth:`submit` finds its payloads
        already in the fetch cache and ships nothing.  Against a plain
        store it is just the submit loop.
        """
        batch = getattr(self.store, "prefetch_batch", None)
        if batch is not None:
            batch([(self.sessions[sid].doc_id, prefix_len)
                   for sid, prefix_len, _, _ in reqs])
        return [self.submit(sid, prefix_len, n_new, greedy=greedy, seed=seed)
                for sid, prefix_len, n_new, seed in reqs]

    # -- delta updates (document edits) ------------------------------------
    def update_document(self, sid: int, new_tokens: np.ndarray):
        """Replace a session's document mid-session, reusing its KV prefix.

        The serving half of the paper's delta-update move: instead of
        treating the edited text as a brand-new document (full rebuild),
        diff old vs new tokens and keep every stored segment strictly
        before the first divergence point — :func:`plan_edit` prices
        reuse-prefix + rebuild-suffix against a from-scratch build in the
        cost model's ``F(n)`` vocabulary and the store :meth:`rekey`\\ s
        the survivors to the edited content's key.  Segments the edit
        invalidates are released from *every* residency tier (device KV,
        host copies, disk spill files) so edited documents never leak
        bytes.

        Works mid-session: any in-flight async build is joined first (its
        store insertions must land before the edit re-keys the index), and
        an in-flight *request* is cancelled — the edit supersedes it, the
        next ``submit`` serves the new content.  Returns the
        :class:`~repro.core.planner.EditPlan` for observability.
        """
        from repro.core.planner import plan_edit

        s = self.sessions[sid]
        if s.ticket is not None:
            # the build's chunk segments belong to the *old* content; land
            # them (and everyone ahead in FIFO) so the edit plan sees them
            # and rekey/release governs their fate like any stored segment
            self._flush_tickets()
            self._join_ticket(s)
        self._flush_packs([g for g in self._packs if sid in g])
        if s.busy:
            # the edit supersedes the in-flight request: its remaining
            # tokens would continue the old text
            s.remaining = 0
            s.mat_pending = False
            self.sched.edit_cancelled += 1
        elif s.mat_pending:
            # materialize first — it can advance the session onto its
            # generated continuation (changing s.doc/s.doc_id), and the
            # edit must diff against the document the session now serves
            self._materialize_decode(s)
        new_doc = np.asarray(new_tokens, np.int32)
        old_id = s.doc_id
        new_id = doc_key(new_doc, s.extras)
        eplan = plan_edit(s.doc, new_doc, self.store.index(old_id),
                          self.cost, self.store.segment_bytes(old_id))
        if new_id != old_id:
            if eplan.action == "edit":
                self.store.rekey(old_id, new_id, upto=eplan.divergence)
            if all(o.doc_id != old_id for o in self.sessions.values()
                   if o.sid != sid):
                # nobody else serves the old content: drop its leftover
                # index (the orphans) from every tier, and its stale
                # admission-prior stats with it
                self.store.release_doc(old_id)
        s.doc, s.doc_id = new_doc, new_id
        s.caches = None
        s.logits = None
        s.greedy_next = None
        s.pos = 0
        s.fork_owned = False    # edited content arrived from outside
        self.sched.edits += 1
        self.sched.edit_reused_segments += len(eplan.reuse)
        self.sched.edit_orphaned += len(eplan.orphans)
        return eplan

    # -- scheduler (pipeline stages 2+3) -----------------------------------
    def _flush_tickets(self) -> None:
        """Finalize outstanding builds' store insertions, FIFO.

        Non-blocking: the deferred trees are lazy jax arrays and byte
        accounting is shape metadata, so this never waits on the device —
        it only makes the store state catch up to what the synchronous
        loop would hold at the same point, releasing each build's pins.
        """
        while self._tickets:
            self.builder.finalize_build(self._tickets.pop(0).pending)

    def _join_ticket(self, s: Session) -> None:
        """Join a ticketed session into the decode stage.

        The compute-side barrier of the pipeline: the session's first
        decode consumes the build's logits/caches, so wait for them here
        (a no-op when the ready-poll triggered the join) and attribute the
        wait to the build, not to the decode lanes.
        """
        t = s.ticket
        t0 = time.perf_counter()
        jax.block_until_ready(s.logits)
        wait = time.perf_counter() - t0
        t.join_wait_s = wait
        t.joined = True
        s.ticket = None
        s.stats.prefill_s += wait
        self.sched.tickets_joined += 1
        self.sched.join_wait_s += wait

    def step(self) -> int:
        """One scheduling round: sample a token for every decodable session,
        then coalesce the still-running ones into batched decode calls.
        Returns the number of tokens produced (0 = idle).

        Sessions whose async build is still in flight are skipped — warm
        sessions keep decoding at full batch while builds run — unless
        nothing else can decode, in which case the oldest ticket is joined
        (blocking) so the loop always makes progress.
        """
        self._flush_tickets()
        busy = [s for s in self.sessions.values() if s.busy]
        if not busy:
            return 0
        ready = [s for s in busy if s.ticket is None]
        waiting = sorted((s for s in busy if s.ticket is not None),
                         key=lambda s: s.ticket.seq)
        for s in waiting:
            # join-before-first-decode: enter the decode lanes as soon as
            # the build's result is ready (non-blocking poll); force-join
            # the oldest ticket when the decode lanes would otherwise idle
            if s.ticket.ready() or not ready:
                self._join_ticket(s)
                ready.append(s)
        in_flight = sum(1 for s in busy if s.ticket is not None)
        for s in ready:
            self._sample(s)
        decode_set = [s for s in ready if s.remaining > 0]
        t0 = time.perf_counter()
        for group in self._plan_groups(decode_set):
            self._decode_group(group)
        dt = time.perf_counter() - t0
        self.stats.decode_s += dt
        for s in decode_set:
            s.stats.decode_s += dt / len(decode_set)
        if in_flight and decode_set:
            self.sched.overlap_steps += 1
            self.sched.overlap_rows += len(decode_set)
        return len(ready)

    def run(self) -> dict[int, list[int]]:
        """Drain every pending request; returns {sid: generated tokens}."""
        while self.step():
            pass
        self._release_idle()
        return {sid: list(s.out_tokens) for sid, s in self.sessions.items()}

    def _release_idle(self) -> None:
        """Free decode-time device memory of drained sessions.

        A finished request's per-session caches and its final pack rows are
        never read again — the next submit replans the prefix from the
        (store-resident) segments — so holding them would pin KV for idle
        tenants indefinitely in a long-running server.  Before release,
        each drained request's generated KV is sliced back into the store
        (:meth:`_materialize_decode`), so dropping the live cache loses
        nothing a follow-up request could have reused.
        """
        idle_groups = [g for g in self._packs
                       if all(sid not in self.sessions
                              or not self.sessions[sid].busy for sid in g)]
        if self.decode_materialize:
            # flush (not just drop) the packs: the rows hold the
            # decode-written KV that materialization slices from
            self._flush_packs(idle_groups)
        else:
            for g in idle_groups:       # rows are never read again: drop
                del self._packs[g]
        for s in self.sessions.values():
            if not s.busy:
                if s.mat_pending:
                    self._materialize_decode(s)
                s.caches = None
                s.logits = None
                s.greedy_next = None

    def _materialize_decode(self, s: Session) -> None:
        """Write a drained request's decode-generated KV back into the store.

        Decode wrote KV for positions ``[req_prefix, pos)`` — every emitted
        token except the last, whose KV was never computed — into the
        session cache.  That slice *is* a valid segment of the generated
        continuation ``doc[:req_prefix] + out_tokens``, so it is stored
        under that continuation's content key (a fork: the base document's
        own positions ≥ req_prefix may hold different text).  Admission is
        the unified cost model's call (paper §5 vocabulary: store only if
        the expected reuse benefit F(n) − C(bytes) is worth it); the base
        document's prefix segments are aliased into the fork's index so a
        follow-up request over generated context plans fully from the
        store.  When the request covered the whole document, the session
        itself advances onto the continuation: its next request may address
        the generated tokens directly.
        """
        s.mat_pending = False
        if not self.decode_materialize or s.caches is None or not s.out_tokens:
            return
        start, end = s.req_prefix, s.pos
        ext_doc = np.concatenate(
            [s.doc[:start], np.asarray(s.out_tokens, np.int32)])
        ext_id = doc_key(ext_doc, s.extras)
        # the continuation is a real document either way: share the base
        # prefix segments with it, and advance the session onto it when the
        # request covered the whole document (follow-ups then address the
        # generated tokens; if admission rejects below, they re-prefill
        # them — the document extends, only its KV is deemed not worth
        # storing)
        self.store.alias(s.doc_id, ext_id, upto=start)
        if start == len(s.doc):
            old_id = s.doc_id
            s.doc, s.doc_id = ext_doc, ext_id
            if s.fork_owned and all(
                    o.doc_id != old_id for o in self.sessions.values()
                    if o.sid != s.sid):
                # the fork this session advanced off is private generated
                # content nobody else serves: retire its document id so a
                # long generation chain doesn't grow per-segment alias sets
                # and dead indexes without bound (the segments themselves
                # survive under the new fork's references)
                self.store.release_doc(old_id)
            s.fork_owned = True
        n_gen = end - start
        if n_gen <= 0:
            return  # 1-token request: nothing was ever decoded into the cache
        # emit a bucket-shaped segment: pad to the store's capacity *before*
        # the admission check so admission prices the bytes that would
        # actually become resident, and the put stores the padded tree
        # as-is (no second pad).  The admission prior is the document's
        # observed reuse rate (static under REPRO_ADMIT_PRIOR=static).
        seg = pad_cache_to(slice_cache(s.caches, start, end),
                           self.store.bucket_capacity(n_gen))
        if not self.cost.admit(n_gen, cache_nbytes(seg),
                               expected_reuses=self.store.admission_prior(ext_id)):
            self.sched.decode_rejects += 1
            return
        self.store.put(Range(start, end), seg, doc_id=ext_id,
                       created_by=s.sid)
        self.sched.decode_segments += 1

    # -- internals ---------------------------------------------------------
    def _sample(self, s: Session) -> None:
        if s.greedy and s.greedy_next is not None:
            tok = s.greedy_next  # batched argmax from the last decode call
        elif s.greedy:
            tok = int(jnp.argmax(s.logits, axis=-1)[0])
        else:
            s.key, sub = jax.random.split(s.key)
            tok = int(jax.random.categorical(sub, s.logits).astype(jnp.int32)[0])
        s.greedy_next = None
        s.next_tok = tok
        s.out_tokens.append(tok)
        s.remaining -= 1
        s.stats.tokens_decoded += 1
        if s.remaining == 0:
            s.mat_pending = True  # written back once the pack is flushed

    def _plan_groups(self, decode_set: list) -> list[tuple[int, ...]]:
        """Partition ready sessions into batchable groups of ≤ max_batch.

        Sessions batch together when they share a cache tree signature.
        Under the ragged decode paths (``merge_decode_packs``, the default
        for kernel/blocked modes) that is the *whole* key: mixed-capacity
        sessions merge into one pack padded to the group's max bucket —
        KV tiles past a row's ``pos`` are skipped (kernel) or exact-zero
        no-ops (blocked), so the padding costs ~nothing and effective
        batch size rises on mixed short/long traffic.

        Under the legacy dense path every row pays the pack's full padded
        capacity, so there the bucketed KV capacity stays part of the key:
        coalescing a 2048-token session with 256-token ones would pad
        every short row to 2048 and multiply the whole pack's attention
        cost — warm decode throughput must hold steady when a long cold
        session joins mid-stream, not degrade to the newcomer's length.
        Grouping never affects tokens either way (batched decode is
        bit-identical to single-session decode regardless of pack
        membership or padded capacity — see ``attn.decode_attention``).
        """
        by_sig: dict[tuple, list] = {}
        if self.merge_decode_packs:
            # merged packs order rows by bucketed capacity, largest first,
            # so the tiered blocked path can slice each KV block down to
            # just the rows whose capacity reaches it; sid breaks ties so
            # an unchanged membership keeps a deterministic (pack-stable)
            # tuple.  Row order never affects tokens — each row's decode
            # is independent of its pack position.
            order = lambda s: (-self._row_cap(s), s.sid)
        else:
            order = lambda s: s.sid
        for s in sorted(decode_set, key=order):
            sig = batch_signature(s.caches)
            if self.merge_decode_packs:
                key: tuple = (sig,)
            else:
                key = (sig, self._row_cap(s))
            by_sig.setdefault(key, []).append(s)
        groups: list[tuple[int, ...]] = []
        for members in by_sig.values():
            for i in range(0, len(members), self.max_batch):
                groups.append(tuple(s.sid for s in members[i:i + self.max_batch]))
        # groups partition the decode set, so an unchanged tuple keeps its
        # pack as-is; only stale packs are split back and new ones built
        new_set = set(groups)
        stale = [g for g in self._packs if g not in new_set]
        if stale:
            self._flush_packs(stale)
        for g in groups:
            if g not in self._packs:
                self._build_pack(g)
        return groups

    def _row_cap(self, s: Session) -> int:
        """A session's bucketed KV capacity — its tier in a merged pack."""
        return bucket_len(max(s.capacity, cache_len(s.caches)),
                          self.decode_bucket)

    def _build_pack(self, group: tuple[int, ...]) -> None:
        sess = [self.sessions[sid] for sid in group]
        target = max(max(s.capacity, cache_len(s.caches)) for s in sess)
        cap = bucket_len(target, self.decode_bucket)
        self._packs[group] = batch_caches(
            [pad_cache_to(s.caches, cap) for s in sess],
            owned=self._donate_decode)
        self.sched.pack_rebuilds += 1

    def _flush_packs(self, groups: Optional[list] = None) -> None:
        """Write batched caches back into their sessions (pre-regroup)."""
        targets = list(self._packs) if groups is None else list(groups)
        for group in targets:
            rows = split_caches(self._packs[group], len(group))
            for sid, row in zip(group, rows):
                if sid in self.sessions:
                    self.sessions[sid].caches = row
            del self._packs[group]

    def _decode_group(self, group: tuple[int, ...]) -> None:
        sess = [self.sessions[sid] for sid in group]
        caches = self._packs[group]
        toks = jnp.asarray([[s.next_tok] for s in sess], jnp.int32)
        pos = jnp.asarray([s.pos for s in sess], jnp.int32)
        pack_cap = cache_len(caches)
        row_caps = None
        if self.decode_mode == "blocked":
            # static per-row KV capacities, non-increasing by construction
            # (_plan_groups sorts merged packs largest-first; split packs
            # are uniform): opts decode_step into the tiered blocked
            # attention + in-place ragged cache update where the model
            # supports it
            row_caps = tuple(min(self._row_cap(s), pack_cap) for s in sess)
        logits, caches = self._jit_decode(self.params, caches, toks, pos,
                                          row_caps=row_caps)
        self._packs[group] = caches
        # one host transfer for the whole batch, then zero-dispatch numpy
        # row views — per-row jnp slicing/argmax costs an eager dispatch
        # each (~0.2 ms on CPU), which at one token per step dwarfs the
        # decode math itself.  numpy argmax breaks ties first-index like
        # jnp, so greedy streams are unchanged.
        logits_np = np.asarray(logits)
        greedy_toks = logits_np.argmax(-1)
        for i, s in enumerate(sess):
            s.logits = logits_np[i:i + 1]
            s.greedy_next = int(greedy_toks[i])
            s.pos += 1
        self.sched.decode_calls += 1
        self.sched.decode_rows += len(group)
        # ragged-decode accounting: live KV per row (post-increment pos is
        # exactly the tokens attended this step) vs the padded capacity
        # every row rides at, plus an attention-FLOP estimate honoring
        # what the routed decode path actually computed
        cap = pack_cap
        live = [s.pos for s in sess]
        self.sched.decode_valid_tokens += sum(live)
        self.sched.decode_padded_tokens += cap * len(sess)
        self.sched.decode_attn_flops += self._decode_attn_flops(
            live, cap, row_caps)

    def _decode_attn_flops(self, live: list[int], cap: int,
                           row_caps=None) -> float:
        """Attention MACs×2 one decode call executed (host-side estimate).

        Per attended KV token a query row does 2 matmuls (q·k and p·v) of
        ``hd`` MACs across ``H`` heads → 4·H·hd FLOPs.  How many KV tokens
        a row touches depends on the routed path: 'dense' reads the full
        padded capacity, 'blocked' stops after the pack's last live
        256-block, 'kernel' stops per row (ragged early-exit).
        """
        from repro.kernels.decode_attention.kernel import DECODE_CHUNK
        from repro.kernels.decode_attention.ref import DECODE_BLOCK

        cfg = self.model.cfg
        per_tok = 4.0 * cfg.n_heads * cfg.head_dim * self._n_attn_layers
        if self.decode_mode == "dense":
            tokens = cap * len(live)
        elif self.decode_mode == "blocked":
            if row_caps is not None:
                # tiered: each row reads 256-blocks up to its own capacity
                tokens = sum(min(bucket_len(c, DECODE_BLOCK), cap)
                             for c in row_caps)
            else:
                blk = ((max(live) + DECODE_BLOCK - 1)
                       // DECODE_BLOCK * DECODE_BLOCK)
                tokens = min(blk, bucket_len(cap, DECODE_BLOCK)) * len(live)
        else:
            chunk = min(DECODE_CHUNK, cap)
            tokens = sum(min((t + chunk - 1) // chunk * chunk, cap)
                         for t in live)
        return per_tok * tokens

    # -- reporting ---------------------------------------------------------
    def aggregate_stats(self) -> ServeStats:
        """Sum of per-session stats (live and closed) plus decode time."""
        agg = ServeStats()
        _accumulate(agg, self._closed_stats)
        for s in self.sessions.values():
            _accumulate(agg, s.stats)
        agg.decode_s = self.stats.decode_s
        return agg

    def report(self) -> dict:
        """Flat serving report: every value is a finite number.

        The divisions behind each rate are guarded (see ``ServeStats`` /
        ``SchedulerStats`` properties), so an idle server — zero requests,
        zero decode calls, no tickets — reports clean zeros rather than
        NaN/inf; pinned by ``tests/test_multisession.py``.
        """
        agg = self.aggregate_stats()
        sc = self.sched
        st = self.store
        tiers = st.tier_bytes()
        return {
            "requests": agg.requests,
            "tokens_decoded": agg.tokens_decoded,
            "tokens_reused": agg.tokens_reused,
            "tokens_computed": agg.tokens_computed,
            "reuse_frac": agg.reuse_frac,
            "prefill_tok_s": agg.prefill_tok_s,
            # prompt-build weight passes over full stored chunks, their
            # tokens, and the tokens a pass computes on average: several
            # chunks where the model groups them, one where it does not
            "extend_passes": agg.extend_passes,
            "extend_pass_tokens": agg.extend_pass_tokens,
            "tokens_per_extend_pass": agg.tokens_per_extend_pass,
            "decode_tok_s": agg.decode_tok_s,
            "decode_calls": sc.decode_calls,
            "mean_batch": sc.mean_batch,
            "pack_rebuilds": sc.pack_rebuilds,
            # ragged-decode padding waste: valid ÷ padded tokens per round
            # (guarded property — 0.0 on an idle server), raw counters,
            # and the mode-aware attention-FLOP estimate
            "decode_padded_frac": sc.decode_padded_frac,
            "decode_valid_tokens": sc.decode_valid_tokens,
            "decode_padded_tokens": sc.decode_padded_tokens,
            "decode_attn_flops": sc.decode_attn_flops,
            "decode_segments": sc.decode_segments,
            "decode_rejects": sc.decode_rejects,
            "tickets_launched": sc.tickets_launched,
            "tickets_joined": sc.tickets_joined,
            "mean_join_wait_s": sc.mean_join_wait_s,
            "overlap_steps": sc.overlap_steps,
            "overlap_batch": sc.overlap_batch,
            # delta updates: edits applied, prefix segments rekeyed to the
            # edited content, segments invalidated, requests superseded
            "edits": sc.edits,
            "edit_reused_segments": sc.edit_reused_segments,
            "edit_orphaned": sc.edit_orphaned,
            "edit_cancelled": sc.edit_cancelled,
            "rekeyed_segments": st.rekeyed_segments,
            # per-tier occupancy and traffic (device -> host -> disk).
            # All plain ints/floats from counters, so an idle manager
            # reports finite zeros like everything above.
            "device_bytes": tiers["device"],
            "host_bytes": tiers["host"],
            "disk_bytes": tiers["disk"],
            "promotions": st.promotions["host"] + st.promotions["disk"],
            "promotions_host": st.promotions["host"],
            "promotions_disk": st.promotions["disk"],
            "demotions": st.demotions["host"] + st.demotions["disk"],
            "demotions_host": st.demotions["host"],
            "demotions_disk": st.demotions["disk"],
            "prefetches": st.prefetches,
            "spill_writes": st.spill_writes,
            "bg_save_queue": st.writer.depth() if st.writer is not None else 0,
            "bg_saves": st.bg_saves,
            "bg_save_drops": st.bg_save_drops,
            "save_stall_s": st.save_stall_s,
            # segment precision: resident int8 entries, cumulative
            # quantization events / bytes released, and reuse-path
            # dequant count — plain counters, finite when idle
            "quantized_segments": st.quantized_segments(),
            "quantized": st.quantized,
            "quant_bytes_saved": st.quant_bytes_saved,
            "dequants": self.builder.dequants,
            # sharded serving: per-shard occupancy and cross-shard fetch
            # traffic.  A plain store reports the degenerate single-shard
            # shape (same keys, zero fetch traffic), so consumers never
            # branch on store type; every value is a finite counter and
            # the idle-guard holds across shards.
            "fetched_segments": self.builder.fetched_segments,
            **(st.shard_report() if hasattr(st, "shard_report") else {
                "shards": 1,
                "remote_fetches": 0,
                "remote_fetch_wire_bytes": 0,
                "fetched_hits": 0,
                "on_demand_fetches": 0,
                "hedged_fetches": 0,
                "hedge_rebuild_wins": 0,
                "hedge_fetch_wins": 0,
                "cancelled_fetches": 0,
                "dead_shard_skips": 0,
                "put_forwards": 0,
                "put_forward_bytes": 0,
                "cross_shard_alias_skips": 0,
                "cross_shard_rekeys": 0,
                "remote_transfers": 0,
                "remote_fetch_items": 0,
                "remote_fetch_bytes": 0,
                "fetch_ticks": 0,
                "coalesce_violations": 0,
                "max_transfers_per_shard_tick": 0,
                "sim_transfer_s": 0.0,
            }),
        }


def _accumulate(into: ServeStats, src: ServeStats) -> None:
    into.requests += src.requests
    into.tokens_reused += src.tokens_reused
    into.tokens_computed += src.tokens_computed
    into.tokens_decoded += src.tokens_decoded
    into.extend_passes += src.extend_passes
    into.extend_pass_tokens += src.extend_pass_tokens
    into.planner_s += src.planner_s
    into.prefill_s += src.prefill_s
