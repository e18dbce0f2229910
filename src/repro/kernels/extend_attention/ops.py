"""Public wrappers: (B, nb, H, hd) suffix attention over (B, T, KV, hd) KV.

This is the entry point the model's ``prefill_extend`` path routes through
on TPU.  Everything layout-related happens here so the kernel stays a pure
per-stream primitive:

  * (batch, KV-head) pairs are flattened onto the kernel's stream grid;
  * GQA (KV heads < q heads) stacks each KV group's G query heads along
    one stream's q-row axis, so the cache is streamed once per *group*
    (no head expansion is ever materialized — blocked_attention's 1/G KV
    memory-traffic saving carries over to the kernel path);
  * MLA's packed [nope ‖ rope] query/key layout is assembled by
    :func:`extend_attention_mla` (the shared rope key is broadcast across
    heads, and the value head-dim may differ from the QK head-dim);
  * ``t_real`` — the valid KV length of a bucket-padded cache — is passed
    through as a runtime scalar, so one compile serves every chunk.

Off-TPU the kernel runs in Pallas ``interpret`` mode (bit-accurate
correctness harness); see :func:`repro.kernels.common.use_interpret`.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import use_interpret

from .kernel import extend_attention_streams, q_tile


def extend_attention(q, k, v, *, t_real=None, chunk: int = 512,
                     interpret=None):
    """Causal suffix attention (see ref.py for semantics).

    q (B, nb, H, hd); k/v (B, T, KV, hd[_v]) with KV dividing H (GQA heads
    are expanded here).  ``t_real`` (int or traced int32 scalar, default:
    the full KV length) marks the valid KV prefix of a padded cache.
    """
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    b, nb, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv                              # GQA group size (1 = MHA)
    hd_v = v.shape[3]
    if t_real is None:
        t_real = t
    # one stream per (batch, KV head); q head h' = k·g + g' shares KV head
    # k, so the G group heads stack along the stream's q-row axis, within
    # each tile of tq positions (the kernel's tile-major row order)
    tq = q_tile(nb)
    n_t = nb // tq
    qs = q.reshape(b, n_t, tq, kv, g, hd).transpose(0, 3, 1, 4, 2, 5).reshape(
        b * kv, n_t * g * tq, hd)
    ks = k.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    vs = v.transpose(0, 2, 1, 3).reshape(b * kv, t, hd_v)
    if interpret is None:
        interpret = use_interpret()
    out = extend_attention_streams(qs, ks, vs, t_real=t_real, chunk=chunk,
                                   groups=g, interpret=interpret)
    return out.reshape(b, kv, n_t, g, tq, hd_v).transpose(
        0, 2, 4, 1, 3, 5).reshape(b, nb, h, hd_v)


def extend_attention_mla(q_nope, q_rope, k_nope, k_rope, v, *, t_real=None,
                         chunk: int = 512, interpret=None):
    """MLA suffix attention over an expanded latent cache.

    q_nope (B, nb, H, nope); q_rope (B, nb, H, rope); k_nope (B, T, H, nope);
    k_rope (B, T, rope) — the decoupled rope key, shared across heads;
    v (B, T, H, hd_v).  Packs [nope ‖ rope] into one stream so a single
    kernel pass scores both terms; the packed-dim softmax scale equals
    MLA's (nope+rope)^-0.5.
    """
    b, nb, h, _ = q_nope.shape
    t = k_nope.shape[1]
    rope = q_rope.shape[-1]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, rope))],
        axis=-1)
    return extend_attention(q, k, v, t_real=t_real, chunk=chunk,
                            interpret=interpret)
