"""Suffix (extend) attention kernel: shape/dtype sweeps vs the jnp oracle,
plus ops-layer parity vs the model's blocked-softmax path (GQA expansion,
MLA packing, ragged runtime ``t_real`` over bucket-padded caches).

Everything here runs the Pallas kernel in ``interpret=True`` on CPU — the
same code path the TPU executes, minus Mosaic lowering — and is fast-lane
safe (no @slow marks).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.extend_attention import ops
from repro.kernels.extend_attention.kernel import extend_attention_streams
from repro.kernels.extend_attention.ref import extend_attention_ref
from repro.models.attention import blocked_attention


def _rand(shape, dtype, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


def _blocked_oracle(q, k, v, t_real):
    """The model's pure-JAX extend semantics over a padded cache.

    q rows sit at positions [t_real − nb, t_real); KV rows at arange(cap).
    Garbage beyond t_real is excluded by the causal mask alone, exactly as
    on the serving path.
    """
    b, nb = q.shape[:2]
    cap = k.shape[1]
    q_pos = jnp.broadcast_to(t_real - nb + jnp.arange(nb)[None], (b, nb))
    k_pos = jnp.broadcast_to(jnp.arange(cap)[None], (b, cap))
    return blocked_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             q_pos, k_pos, causal=True)


@pytest.mark.parametrize("nb,t", [(8, 8), (16, 48), (8, 200), (32, 257)])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_extend_attention_sweep(nb, t, hd, dtype):
    assert t >= nb
    b, h = 2, 2
    q = _rand((b, nb, h, hd), np.float32, 1).astype(dtype)
    k = _rand((b, t, h, hd), np.float32, 2).astype(dtype)
    v = _rand((b, t, h, hd), np.float32, 3).astype(dtype)
    out = ops.extend_attention(q, k, v, chunk=16)
    ref = extend_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=rtol, atol=rtol)


# ---------------------------------------------------------------------------
# ops-layer parity vs blocked_attention (the serving path's CPU reference)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_heads", [1, 2, 4])       # GQA group sizes 4/2/1
@pytest.mark.parametrize("t_real", [16, 55, 96])      # prefix-empty → full
def test_extend_gqa_parity_vs_blocked(kv_heads, t_real):
    """Padded-cache extend == blocked path across GQA group sizes and
    ragged runtime t_real (nb=16: t_real=16 is a prefix-empty extend,
    t_real=96=cap is prefix-heavy with zero padding)."""
    b, nb, h, hd, cap = 2, 16, 4, 32, 96
    q = _rand((b, nb, h, hd), np.float32, 10)
    k = _rand((b, cap, kv_heads, hd), np.float32, 11)
    v = _rand((b, cap, kv_heads, hd), np.float32, 12)
    out = ops.extend_attention(q, k, v, t_real=t_real, chunk=32,
                               interpret=True)
    ref = _blocked_oracle(q, jnp.repeat(jnp.asarray(k), h // kv_heads, axis=2),
                          jnp.repeat(jnp.asarray(v), h // kv_heads, axis=2),
                          t_real)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t_real", [8, 40, 64])
def test_extend_mla_parity_vs_blocked(t_real):
    """MLA nope/rope packing at the ops layer == the blocked path's concat,
    including a value head-dim different from the QK head-dim."""
    b, nb, h, nope, rope, v_dim, cap = 1, 8, 4, 24, 8, 16, 64
    q_nope = _rand((b, nb, h, nope), np.float32, 20)
    q_rope = _rand((b, nb, h, rope), np.float32, 21)
    k_nope = _rand((b, cap, h, nope), np.float32, 22)
    k_rope = _rand((b, cap, rope), np.float32, 23)
    v = _rand((b, cap, h, v_dim), np.float32, 24)
    out = ops.extend_attention_mla(q_nope, q_rope, k_nope, k_rope, v,
                                   t_real=t_real, chunk=16, interpret=True)
    q = jnp.concatenate([jnp.asarray(q_nope), jnp.asarray(q_rope)], axis=-1)
    k = jnp.concatenate(
        [jnp.asarray(k_nope),
         jnp.broadcast_to(jnp.asarray(k_rope)[:, :, None, :],
                          (b, cap, h, rope))], axis=-1)
    ref = _blocked_oracle(q, k, v, t_real)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_t_real_is_runtime_not_compile_time():
    """One jitted executable serves every t_real of a fixed padded shape."""
    import jax

    b, nb, h, hd, cap = 1, 8, 2, 16, 64
    q = jnp.asarray(_rand((b, nb, h, hd), np.float32, 30))
    k = jnp.asarray(_rand((b, cap, h, hd), np.float32, 31))
    v = jnp.asarray(_rand((b, cap, h, hd), np.float32, 32))
    traces = []

    @jax.jit
    def run(q, k, v, t_real):
        traces.append(1)
        return ops.extend_attention(q, k, v, t_real=t_real, chunk=16,
                                    interpret=True)

    for t_real in (8, 23, 40, 64):
        out = run(q, k, v, jnp.int32(t_real))
        ref = _blocked_oracle(q, k, v, t_real)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
    assert len(traces) == 1, "t_real must not trigger retraces"


def test_streams_accepts_awkward_cache_lengths():
    """No more hard t_pad % chunk assert: internal padding + auto-shrunk
    chunk accept any KV length."""
    s, nb, hd = 2, 4, 16
    for t, chunk in [(5, 512), (200, 64), (47, 16), (64, 512)]:
        if t < nb:
            continue
        q = jnp.asarray(_rand((s, nb, hd), np.float32, 40))
        k = jnp.asarray(_rand((s, t, hd), np.float32, 41))
        v = jnp.asarray(_rand((s, t, hd), np.float32, 42))
        out = extend_attention_streams(q, k, v, t_real=t, chunk=chunk,
                                       interpret=True)
        # per-stream layout: ref wants (B, nb, H, hd) — streams map to B, H=1
        ref = extend_attention_ref(q[:, :, None, :], k[:, :, None, :],
                                   v[:, :, None, :])
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref[:, :, 0, :]),
                                   rtol=1e-4, atol=1e-5)


def test_matches_fresh_prefill_semantics():
    """extend over [prefix ‖ chunk] == the chunk rows of full causal attention."""
    b, h, hd, t, nb = 1, 2, 64, 64, 16
    q_all = _rand((b, t, h, hd), np.float32, 4)
    k = _rand((b, t, h, hd), np.float32, 5)
    v = _rand((b, t, h, hd), np.float32, 6)
    full = extend_attention_ref(jnp.asarray(q_all), jnp.asarray(k), jnp.asarray(v))
    out = ops.extend_attention(q_all[:, -nb:], k, v, chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -nb:]),
                               rtol=1e-4, atol=1e-5)


def test_q_tiles_at_serving_widths():
    """A 512-position extend runs as four q tiles of 128 positions, each
    taking its causal offset from its tile index: 8 query heads over one KV
    head of 128, a 3840-position cache whose valid length is off every
    chunk and tile boundary, against the oracle."""
    from repro.kernels.extend_attention.kernel import q_tile

    b, nb, g, hd, cap, t_real = 1, 512, 8, 128, 3840, 2701
    assert q_tile(nb) == 128 and t_real % 128
    q = _rand((b, nb, g, hd), np.float32, 50)
    k = _rand((b, cap, 1, hd), np.float32, 51)
    v = _rand((b, cap, 1, hd), np.float32, 52)
    out = ops.extend_attention(q, k, v, t_real=t_real, interpret=True)
    ref = extend_attention_ref(jnp.asarray(q), jnp.repeat(jnp.asarray(k), g, 2),
                               jnp.repeat(jnp.asarray(v), g, 2), t_real=t_real)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
