"""Grouped prompt builds: a gap's full chunks run as weight passes of
``GROUP_CHUNKS`` chunks where the model allows it, and give what the
per-chunk passes give.

Every case builds the same request twice on a tiny float32 model: once as
served (groups of four chunks, the chunks left over one pass each) and
once with grouping off (``group_chunks = 1``, one pass a chunk).  The two
must agree on the caches, the first token's logits, three decode steps and
every stored chunk segment, and the pass counters must say how many passes
ran.  SSD and MoE stacks never group, and build exactly as the plain fused
loop does.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.descriptors import Range
from repro.kernels.common import bucket_len
from repro.models.lm import LM
from repro.serve.engine import GROUP_CHUNKS, PrefixCacheBuilder, ServeStats
from repro.serve.kv_cache import SegmentStore, pad_cache_to, slice_cache

CHUNK, RAGGED, N_DECODE = 16, 5, 3
TOL = dict(rtol=1e-4, atol=1e-4)


def _setup(cfg):
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    doc = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 512).astype(np.int32)
    return model, params, doc


@pytest.fixture(scope="module")
def dense():
    return _setup(reduced(ARCHS["deepseek-67b"]))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _build(model, params, doc, prefix_len, *, stored, group, chunk=CHUNK):
    """Serve ``doc[:prefix_len]`` over a store holding ``stored`` (a list of
    (range, caches)); returns (logits, caches padded for the decode steps,
    plan, the chunk segments the build put, stats, builder)."""
    store = SegmentStore()
    for rng, seg in stored:
        store.put(rng, seg, doc_id="d")
    b = PrefixCacheBuilder(model, params, store, chunk_tokens=chunk)
    if not group:
        b.group_chunks = 1
    stats = ServeStats()
    logits, caches, plan, pending = b.prefix_with_logits(
        doc, prefix_len, doc_id="d", stats=stats, capacity=prefix_len,
        defer=True)
    puts = list(pending.puts)
    b.finalize_build(pending)
    caches = pad_cache_to(caches, bucket_len(prefix_len + N_DECODE, 64))
    return logits, caches, plan, puts, stats, b


def _decode(model, params, logits, caches, pos):
    """Greedy steps from ``logits``; returns every step's logits."""
    step = jax.jit(model.decode_step)
    out = []
    for _ in range(N_DECODE):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        logits, caches = step(params, caches, tok, jnp.asarray([pos], jnp.int32))
        out.append(np.asarray(logits))
        pos += 1
    return out


def _position(where: str, r: int):
    """(stored prefix length, gap end) of a gap of 4 + r full chunks.

    ``cold``: the gap follows the cold first chunk; ``stored``: it follows
    a stored segment [0, 48); ``at_cap``: it ends one position short of the
    build's capacity, so a pass that ran past it would be clamped.
    """
    n_full = GROUP_CHUNKS + r
    if where == "cold":
        return 0, CHUNK + n_full * CHUNK + RAGGED
    if where == "stored":
        return 48, 48 + n_full * CHUNK + RAGGED
    hi = 192 - 1                          # capacity bucket_len(192, 64)
    return hi - (CHUNK - 1) - n_full * CHUNK, hi


@pytest.mark.parametrize("r", range(4))
@pytest.mark.parametrize("where", ["cold", "stored", "at_cap"])
def test_grouped_build_matches_per_chunk_passes(dense, where, r):
    model, params, doc = dense
    lo, hi = _position(where, r)
    prefix_len = hi + 1                   # the build covers [0, prefix_len - 1)
    stored = []
    if lo:
        ref = PrefixCacheBuilder(model, params, SegmentStore(),
                                 chunk_tokens=CHUNK)
        full, _ = ref.build_prefix(doc, lo, doc_id="d", materialize=False)
        stored = [(Range(0, lo), slice_cache(full, 0, lo))]
    runs = [_build(model, params, doc, prefix_len, stored=stored, group=g)
            for g in (True, False)]
    (lg, cg, plan, puts_g, st_g, bg), (lc, cc, _, puts_c, st_c, bc) = runs
    if where == "at_cap":
        assert bucket_len(prefix_len, 64) - hi == 1
    gaps = [s.rng for s in plan.steps if s.model_id is None]
    gap_lo = lo or CHUNK
    assert gaps == [Range(lo, hi)] and (hi - gap_lo) // CHUNK == GROUP_CHUNKS + r

    n_full = GROUP_CHUNKS + r
    assert bg.group_chunks == GROUP_CHUNKS
    assert (st_g.extend_passes, st_g.extend_pass_tokens) == (1 + r, n_full * CHUNK)
    assert (st_c.extend_passes, st_c.extend_pass_tokens) == (n_full, n_full * CHUNK)
    assert st_g.tokens_computed == st_c.tokens_computed
    assert bg.lowerings["extend_group"] == 1 and bc.lowerings["extend_group"] == 0
    assert bg.lowerings["extend_many"] == (1 if r else 0)

    for a, b in zip(_leaves(cg), _leaves(cc)):
        np.testing.assert_allclose(a[:, :, :prefix_len], b[:, :, :prefix_len], **TOL)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lc), **TOL)
    for a, b in zip(_decode(model, params, lg, cg, prefix_len),
                    _decode(model, params, lg, cc, prefix_len)):
        np.testing.assert_allclose(a, b, **TOL)

    rngs = [rng for rng, _ in puts_g]
    assert rngs == [rng for rng, _ in puts_c]
    assert all(Range(a, a + CHUNK) in rngs
               for a in range(gap_lo, gap_lo + n_full * CHUNK, CHUNK))
    for (_, a), (_, b) in zip(puts_g, puts_c):
        for x, y in zip(_leaves(a), _leaves(b)):
            assert x.shape == y.shape
            np.testing.assert_allclose(x, y, **TOL)


def test_grouped_build_through_the_extend_kernel(monkeypatch):
    """The Pallas extend kernel (interpret mode) takes a grouped pass of
    256 positions as two q tiles of 128, and agrees with its own per-chunk
    passes."""
    monkeypatch.setenv("REPRO_EXTEND_KERNEL", "1")
    model, params, doc = _setup(reduced(ARCHS["deepseek-67b"]))
    chunk = 64
    prefix_len = chunk + (GROUP_CHUNKS + 1) * chunk + RAGGED + 1
    (lg, cg, _, _, st_g, _), (lc, cc, _, _, _, _) = [
        _build(model, params, doc, prefix_len, stored=[], group=g, chunk=chunk)
        for g in (True, False)]
    assert st_g.extend_passes == 2
    for a, b in zip(_leaves(cg), _leaves(cc)):
        np.testing.assert_allclose(a[:, :, :prefix_len], b[:, :, :prefix_len], **TOL)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lc), **TOL)


def test_latent_attention_stack_groups():
    """A dense MLA stack groups too: its extend reads no per-pass quantity."""
    cfg = dataclasses.replace(reduced(ARCHS["deepseek-v2-236b"]), moe=None,
                              d_ff=128)
    model, params, doc = _setup(cfg)
    assert model.extend_groupable()
    prefix_len = CHUNK + (GROUP_CHUNKS + 2) * CHUNK + RAGGED + 1
    (lg, cg, _, puts_g, st_g, _), (lc, cc, _, puts_c, _, _) = [
        _build(model, params, doc, prefix_len, stored=[], group=g)
        for g in (True, False)]
    assert st_g.extend_passes == 3
    for a, b in zip(_leaves(cg), _leaves(cc)):
        np.testing.assert_allclose(a[:, :, :prefix_len], b[:, :, :prefix_len], **TOL)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lc), **TOL)
    assert [rng for rng, _ in puts_g] == [rng for rng, _ in puts_c]


def _plain_fused_build(model, params, doc, prefix_len, cap):
    """The gap filler with one pass a chunk, written out: the cold first
    chunk, one fused loop over the full chunks, the ragged remainder and
    the one-token boundary extend."""
    hi = prefix_len - 1
    _, caches = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(doc[None, :CHUNK])})
    caches = pad_cache_to(caches, cap)
    n_full = (hi - CHUNK) // CHUNK
    toks = np.zeros((1, cap // CHUNK, CHUNK), np.int32)
    toks[0, :n_full] = doc[CHUNK:CHUNK + n_full * CHUNK].reshape(n_full, CHUNK)
    _, caches, _ = jax.jit(model.prefill_extend_many)(
        params, caches, jnp.asarray(toks), jnp.asarray(CHUNK, jnp.int32),
        jnp.asarray(n_full, jnp.int32))
    extend = jax.jit(model.prefill_extend)
    a = CHUNK + n_full * CHUNK
    _, caches = extend(params, caches, jnp.asarray(doc[None, a:hi]),
                       jnp.asarray(a, jnp.int32))
    return extend(params, caches, jnp.asarray(doc[None, hi:prefix_len]),
                  jnp.asarray(hi, jnp.int32))


@pytest.mark.parametrize("arch", ["mamba2-130m", "mixtral-8x7b"])
def test_ssd_and_moe_stacks_never_group(arch):
    """Running state (SSD) and per-pass expert capacity (MoE) keep one
    pass a chunk, bit for bit the plain fused loop."""
    model, params, doc = _setup(reduced(ARCHS[arch]))
    assert not model.extend_groupable()
    n_full = 2 * GROUP_CHUNKS + 1
    prefix_len = CHUNK + n_full * CHUNK + RAGGED + 1
    logits, caches, _, _, stats, b = _build(
        model, params, doc, prefix_len, stored=[], group=True)
    assert b.group_chunks == 1 and b.lowerings["extend_group"] == 0
    assert (stats.extend_passes, stats.tokens_per_extend_pass) == (n_full, CHUNK)
    want_logits, want = _plain_fused_build(model, params, doc, prefix_len,
                                           bucket_len(prefix_len, 64))
    want = pad_cache_to(want, bucket_len(prefix_len + N_DECODE, 64))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for a, w in zip(_leaves(caches), _leaves(want)):
        np.testing.assert_array_equal(a, w)
