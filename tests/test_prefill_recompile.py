"""Recompile-count regression: incremental prefill compiles O(#buckets)
executables, not O(#chunks).

Before the bucketed extend path, ``prefill_extend`` was jitted with a
static ``start`` over a cache that grew every chunk, so a cold N-chunk
prefill paid N distinct XLA lowerings — the incremental step cost more in
compiles than recomputation cost in FLOPs (the exact inversion of the
paper's Alg 2 economics).  These tests pin the fix: one shape-stable
executable per (cache bucket, chunk shape), counted via the builder's
trace-counting wrappers.  Dense attention stacks fill a gap's whole
groups of four chunks through one more executable per cache bucket (the
grouped weight pass), counted apart as ``extend_group``.
"""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.descriptors import Range
from repro.models.lm import LM
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import SegmentStore, cache_len, slice_cache
from repro.serve.session import SessionManager


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["deepseek-67b"])
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, cfg.vocab_size, 320).astype(np.int32)
            for _ in range(4)]
    return cfg, model, params, docs


def test_cold_multidoc_serve_lowerings_bounded_by_buckets(setup):
    """Cold-serving several documents shares one executable set: the
    lowering count stays flat while the chunk count grows per document."""
    cfg, model, params, docs = setup
    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=64)
    sids = [mgr.add_session(d) for d in docs[:3]]
    for sid in sids:                     # identical request shape, cold docs
        mgr.submit(sid, 256, 2)
        mgr.run()
    agg = mgr.aggregate_stats()
    chunks = agg.tokens_computed // 32
    low = mgr.builder.lowerings
    # per cold doc: prefill [0,32), one grouped pass for [32,160), one
    # fused extend_many for [160,224), one ragged remainder [224,254), one
    # 1-token boundary extend — all five executables are shared across the
    # three documents
    assert chunks >= 3 * 8, f"expected ≥24 chunks of work, got {chunks}"
    assert low["extend_many"] == 1, low
    assert low == {"prefill": 1, "extend": 2, "extend_many": 1,
                   "extend_group": 1, "insert": 0}, (
        f"cold prefill must compile O(#buckets) executables, "
        f"got {low} for {chunks} chunks")


def test_new_length_same_bucket_adds_no_gap_loop_compile(setup):
    """A different document served at a different chunk-aligned length in
    the same capacity bucket reuses the fused gap-loop executable."""
    cfg, model, params, docs = setup
    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=64)
    s1 = mgr.add_session(docs[0])
    mgr.submit(s1, 256, 2)
    mgr.run()
    before = dict(mgr.builder.lowerings)
    s2 = mgr.add_session(docs[1])
    mgr.submit(s2, 224, 34)              # same 320-bucket: 224+34 and 256+2
    mgr.run()
    after = mgr.builder.lowerings
    assert after["extend_many"] == before["extend_many"], (before, after)
    assert after["extend_group"] == before["extend_group"] == 1, (before, after)
    assert after["prefill"] == before["prefill"], (before, after)


def test_multi_gap_plan_single_dispatch_per_gap(setup):
    """A plan with interleaved reuse/gap steps fills every gap through the
    same fused executable and inserts segments without recompiling per
    position; the result matches a cold build exactly."""
    cfg, model, params, docs = setup
    doc = docs[0]
    # reference build to carve mid-document segments from
    ref = ServeEngine(model, params, doc, chunk_tokens=32)
    ref_caches, _ = ref.build_prefix(256)

    store = SegmentStore()
    store.put(Range(64, 96), slice_cache(ref_caches, 64, 96), doc_id="d")
    store.put(Range(160, 192), slice_cache(ref_caches, 160, 192), doc_id="d")
    eng = ServeEngine(model, params, doc, chunk_tokens=32, store=store,
                      doc_id="d")
    caches, plan = eng.build_prefix(256)
    gaps = [s for s in plan.steps if s.model_id is None]
    assert len(gaps) >= 2, "plan should interleave reuse and gaps"
    low = eng.builder.lowerings
    # gaps [0,64), [96,160), [192,256): one prefill + one shared fused
    # loop (no gap holds a group of four chunks); the two 32-token segment
    # inserts share one executable
    assert low["extend_many"] == 1, low
    assert low["insert"] <= 1, low
    assert low == {"prefill": 1, "extend": 0, "extend_many": 1,
                   "extend_group": 0, "insert": 1}, low
    assert cache_len(caches) == cache_len(ref_caches)
    np.testing.assert_allclose(
        np.asarray(caches[0]["p0"]["k"][:, :, :256]),
        np.asarray(ref_caches[0]["p0"]["k"][:, :, :256]),
        rtol=1e-5, atol=1e-5)


def test_grouped_pass_compiles_once_per_capacity(setup):
    """The grouped weight pass is one executable per cache capacity: cold
    documents whose gaps hold a group at two capacities build two, and a
    third at one of them builds none."""
    cfg, model, params, docs = setup
    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=64)
    # prefix 256 (+2) rides at 320; prefix 192 (+2) and 200 (+2) at 256;
    # each gap after the cold first chunk holds 4-6 full chunks
    for doc, prefix in zip(docs[:3], (256, 192, 200)):
        mgr.submit(mgr.add_session(doc), prefix, 2)
        mgr.run()
        if prefix == 192:
            assert mgr.builder.lowerings["extend_group"] == 2
    low = mgr.builder.lowerings
    assert low["extend_group"] == 2, low
    rep = mgr.report()
    # passes: a group and 2 chunks; a group; a group and 1 chunk
    assert rep["extend_passes"] == 3 + 1 + 2, rep
    assert rep["extend_pass_tokens"] == (6 + 4 + 5) * 32, rep


def test_ragged_segment_hits_compile_per_bucket_not_per_length(setup):
    """The reuse path is shape-stable over (bucket, valid-length) pairs:
    replaying hits on segments of many distinct ragged lengths compiles the
    jitted insert_cache once per bucket pair, not once per length.

    Before the bucketed store layout, every distinct stored segment length
    was a fresh input signature for the jitted insert — a warm server's
    *cheapest* requests paid the recompiles its cold path had been cured
    of in PR 2."""
    cfg, model, params, docs = setup
    doc = docs[0]
    ref = ServeEngine(model, params, doc, chunk_tokens=32)
    ref_caches, _ = ref.build_prefix(256)

    # ragged tiling of [0, 231): five distinct valid lengths, one 32-token
    # bucket (plus one 64-bucket segment), contiguous so the plan can be
    # pure reuse
    bounds = [0, 21, 44, 69, 96, 125, 189, 231]
    store = SegmentStore(seq_bucket=32)
    for lo, hi in zip(bounds, bounds[1:]):
        store.put(Range(lo, hi), slice_cache(ref_caches, lo, hi), doc_id="d")
    lengths = {hi - lo for lo, hi in zip(bounds, bounds[1:])}
    assert len(lengths) >= 5, "trace must exercise many distinct lengths"

    eng = ServeEngine(model, params, doc, chunk_tokens=32, seq_bucket=64,
                      store=store, doc_id="d")
    caches, plan = eng.build_prefix(231)
    assert all(s.model_id is not None for s in plan.steps), \
        "full coverage: every step should be a store hit"
    seg_buckets = {store.capacity(s.model_id) for s in plan.steps}
    low = eng.builder.lowerings
    # O(#bucket pairs): at most one insert executable per distinct stored
    # segment capacity (one destination capacity here), NOT per length
    assert low["insert"] <= len(seg_buckets) < len(lengths), low
    # and the assembled prefix is exact: padded-tail garbage from each
    # insert was overwritten by the next step's valid rows
    np.testing.assert_allclose(
        np.asarray(caches[0]["p0"]["k"][:, :, :231]),
        np.asarray(ref_caches[0]["p0"]["k"][:, :, :231]),
        rtol=1e-5, atol=1e-5)

    # a second document tiled at *different* ragged lengths in the same
    # buckets replays through the same builder with no new executable:
    # the warm path is compile-once over buckets, like the cold path
    ref2 = ServeEngine(model, params, docs[1], chunk_tokens=32)
    ref2_caches, _ = ref2.build_prefix(256)
    bounds2 = [0, 25, 48, 75, 107, 138, 189, 231]
    for lo, hi in zip(bounds2, bounds2[1:]):
        store.put(Range(lo, hi), slice_cache(ref2_caches, lo, hi),
                  doc_id="d2")
    assert {hi - lo for lo, hi in zip(bounds2, bounds2[1:])} != lengths
    before = dict(eng.builder.lowerings)
    caches2, plan2 = eng.builder.build_prefix(docs[1], 231, doc_id="d2")
    assert all(s.model_id is not None for s in plan2.steps)
    assert eng.builder.lowerings == before, (before, eng.builder.lowerings)
    np.testing.assert_allclose(
        np.asarray(caches2[0]["p0"]["k"][:, :, :231]),
        np.asarray(ref2_caches[0]["p0"]["k"][:, :, :231]),
        rtol=1e-5, atol=1e-5)


def test_edit_rebuild_adds_no_lowerings(setup):
    """An edit-rebuild is suffix work over already-compiled shapes: the
    rekeyed prefix enters through the shared insert executable and the
    suffix fills through the same fused extend path, so serving an edited
    document compiles nothing beyond the warm (bucket, chunk) set."""
    cfg, model, params, docs = setup
    doc = docs[2]
    mgr = SessionManager(model, params, chunk_tokens=32, decode_bucket=64)
    sid = mgr.add_session(doc)
    mgr.submit(sid, 256, 2)              # cold: compiles the executable set
    mgr.run()
    mgr.submit(sid, 256, 2)              # warm replay: compiles the insert
    mgr.run()
    before = dict(mgr.builder.lowerings)

    new_doc = doc.copy()                 # chunk-aligned edit at 60% depth
    new_doc[160] = (new_doc[160] + 1) % cfg.vocab_size
    ep = mgr.update_document(sid, new_doc)
    assert ep.action == "edit" and ep.reused_tokens >= 128
    mgr.submit(sid, 256, 2)
    mgr.run()
    assert mgr.sessions[sid].stats.tokens_reused >= ep.reused_tokens
    assert mgr.builder.lowerings == before, (before, mgr.builder.lowerings)
