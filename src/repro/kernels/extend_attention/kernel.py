"""Suffix (extend) attention — the compute inside incremental prefix
construction (serve engine's ``prefill_extend`` gap-filler).

The serving engine realizes the paper's reuse plan by *extending* a cached
prefix with an uncovered chunk: the chunk's q rows attend over
[cached prefix ‖ new chunk].  On TPU that inner loop is this kernel:

  * one grid step = one (batch·head) stream and one tile of at most
    ``Q_TILE`` query positions — the stream axis maps onto the mesh's
    data/model axes at the distribution layer, and a stream's KV block
    index does not change between its q tiles, so the block is fetched
    once per stream however many tiles read it;
  * the q tile is pinned in VMEM; the KV stream is walked in
    ``chunk``-sized VMEM tiles with online softmax (m, l, acc carries in
    registers/VMEM — nothing quadratic is ever materialized), so VMEM is
    bounded by the tile, not by the number of query positions;
  * the causal boundary only affects the trailing ``nb`` positions, so all
    fully-cached tiles run mask-free on the MXU;
  * ``t_real`` — the valid KV length — is a **runtime scalar** (SMEM via
    scalar prefetch), so one compiled executable serves every chunk of a
    bucket-padded cache: the caller pads KV to a fixed capacity and only
    the mask moves between calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, round_up

NEG_INF = -1e30

#: query positions per grid step: a 128-position tile of 8 GQA heads is
#: 1,024 q rows, whose (rows, chunk) f32 scores fit VMEM at head dim 128
Q_TILE = 128


def q_tile(nb: int) -> int:
    """Query positions per tile: ``Q_TILE`` where it divides ``nb``, else
    all ``nb`` in one tile (ragged and one-token extends)."""
    return Q_TILE if nb % Q_TILE == 0 else nb


def _kernel(t_ref, q_ref, k_ref, v_ref, o_ref, *, chunk: int, groups: int,
            nb: int):
    rows, hd = q_ref.shape[1], q_ref.shape[2]
    tq = rows // groups              # q positions in this tile
    hd_v = v_ref.shape[2]
    t_pad = k_ref.shape[1]
    n_chunks = t_pad // chunk
    t_real = t_ref[0]                                    # runtime valid length

    q = q_ref[0].astype(jnp.float32) * (hd ** -0.5)      # (rows, hd) in VMEM
    # GQA: the tile carries all `groups` query heads of one KV head,
    # stacked as row r = g·tq + i — so row r's position in the tile is
    # r mod tq, and tile j starts tq·j positions into the nb-long block
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0)
    q_pos = (t_real - nb + pl.program_id(1) * tq) + (
        row % tq if groups > 1 else row)

    def body(i, carry):
        m, l, acc = carry
        kc = k_ref[0, pl.dslice(i * chunk, chunk), :].astype(jnp.float32)
        vc = v_ref[0, pl.dslice(i * chunk, chunk), :].astype(jnp.float32)
        sc = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (rows, chunk)
        k_pos = i * chunk + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        valid = (k_pos <= q_pos) & (k_pos < t_real)
        sc = jnp.where(valid, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jax.lax.dot_general(p, vc, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * corr[:, None] + pv)

    m0 = jnp.full((rows,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows,), jnp.float32)
    a0 = jnp.zeros((rows, hd_v), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "groups", "interpret"))
def extend_attention_streams(q, k, v, *, t_real, chunk: int = 512,
                             groups: int = 1, interpret: bool = False):
    """Per-stream suffix attention.  q (S, G·nb, hd); k/v (S, T, hd[_v]).

    ``t_real`` is the valid KV length — an int or a traced int32 scalar;
    positions ≥ ``t_real`` are masked, so ``k``/``v`` may carry arbitrary
    padding.  KV is padded internally to a ``chunk`` multiple and ``chunk``
    auto-shrinks when the stream is shorter than one tile, so any cache
    length is accepted.

    ``groups`` > 1 is the GQA layout: one stream carries all G query heads
    of a single KV head, stacked along the q-row axis — the KV stream is
    read once per *group* instead of once per query head, preserving
    blocked_attention's 1/G KV memory-traffic saving on the kernel path.
    The q rows run in tiles of ``tq = q_tile(nb)`` positions, tile-major:
    row j·G·tq + g·tq + i is head g at sequence position j·tq + i (with one
    tile, row g·nb + i).  Each tile is a grid step of its own.
    """
    s, rows, hd = q.shape
    nb = rows // groups
    tq = q_tile(nb)
    t = k.shape[1]
    hd_v = v.shape[2]
    chunk = min(chunk, round_up(t, 8))                   # auto-shrink for short KV
    t_pad = round_up(t, chunk)
    if t_pad != t:                                       # mask covers the pad
        k = pad_axis(k, 1, t_pad)
        v = pad_axis(v, 1, t_pad)
    kern = functools.partial(_kernel, chunk=chunk, groups=groups, nb=nb)
    tile = groups * tq
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                           # t_real rides in SMEM
        grid=(s, nb // tq),
        in_specs=[
            pl.BlockSpec((1, tile, hd), lambda i, j, t: (i, j, 0)),
            # the same block for every q tile of a stream: fetched once
            pl.BlockSpec((1, t_pad, hd), lambda i, j, t: (i, 0, 0)),
            pl.BlockSpec((1, t_pad, hd_v), lambda i, j, t: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, hd_v), lambda i, j, t: (i, j, 0)),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, rows, hd_v), q.dtype),
        interpret=interpret,
    )(jnp.asarray(t_real, jnp.int32).reshape(1), q, k, v)
