"""Chunked logistic-regression SGD with the chunk resident in VMEM.

The paper's Alg 1 outer loop is embarrassingly parallel; its inner loop is
a sequential minibatch-SGD pass over one chunk.  On TPU the right cut is:
**one grid step = one chunk**, the whole ``(l, d)`` chunk pinned in VMEM so
the sequential pass never re-touches HBM (the 2015 version re-read rows
from the buffer pool every update).  Chunks map onto the grid — which also
maps onto the mesh's data axis at the distribution layer — and the VPU/MXU
handle the (batch, d) minibatch math.

VMEM budget: chunk (l·d) + weights; l·d ≤ ~1.5M fp32 (≈6 MB) keeps a
comfortable margin, asserted in the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
#: full f32 MXU passes: by default Mosaic rounds f32 operands to bf16 for
#: a single pass, which would cost the statistics ~3 significant digits
_F32 = jax.lax.Precision.HIGHEST


def _kernel(x_ref, y_ref, m_ref, w_ref, b_ref, *, lam: float, lr: float, batch: int):
    d = x_ref.shape[2]
    steps = y_ref.shape[1]
    contract_d = (((1,), (1,)), ((), ()))        # (1, d) · (batch, d)ᵀ
    contract_b = (((1,), (0,)), ((), ()))        # (1, batch) · (batch, d)

    def body(t, carry):
        w, b = carry                             # (1, d), (1, 1)
        start = pl.multiple_of(t * batch, batch)
        xb = x_ref[0, pl.ds(start, batch), :]    # (batch, d) — VMEM resident
        yb = y_ref[0, pl.ds(t, 1), :]            # (1, batch): minibatch t's row
        mb = m_ref[0, pl.ds(t, 1), :]
        z = jax.lax.dot_general(w, xb, contract_d, precision=_F32,
                                preferred_element_type=jnp.float32) + b
        g = (jax.nn.sigmoid(z) - yb) * mb
        live = mb.sum(axis=1, keepdims=True)
        denom = jnp.maximum(live, 1.0)
        tf = jnp.full((1, 1), t, jnp.int32).astype(jnp.float32)
        # a minibatch with no real row (a short chunk's tail) takes no step:
        # the decay 2·lam·w alone would otherwise still move w
        step = jnp.where(live > 0.0, lr / jnp.sqrt(tf + 1.0), 0.0)
        gw = jax.lax.dot_general(g, xb, contract_b, precision=_F32,
                                 preferred_element_type=jnp.float32) / denom
        gw = gw + 2.0 * lam * w
        gb = g.sum(axis=1, keepdims=True) / denom
        return (w - step * gw, b - step * gb)

    w0 = jnp.zeros((1, d), jnp.float32)
    b0 = jnp.zeros((1, 1), jnp.float32)
    w, b = jax.lax.fori_loop(0, steps, body, (w0, b0))
    w_ref[0] = w
    b_ref[0] = jnp.broadcast_to(b, b_ref.shape[1:])


@functools.partial(
    jax.jit, static_argnames=("lam", "lr", "batch", "interpret")
)
def sgd_chunks(x, y, mask, *, lam: float, lr: float, batch: int, interpret: bool = False):
    """Run one SGD epoch per chunk.

    ``x`` (p, l, d); ``y``/``mask`` (p, l // batch, batch) — one row per
    minibatch, so the kernel reads minibatch t as a row slice; a minibatch
    whose mask is all zero leaves the weights as they were.  Returns
    weights (p, 1, d) and bias (p, 1, 128), the bias broadcast across its
    lane row.  Every block's trailing two dims equal the array's, which is
    the TPU tiling rule for blocks narrower than (8, 128).
    """
    p, l, d = x.shape
    assert l % batch == 0 and d % 128 == 0, (l, d, batch)
    assert y.shape == mask.shape == (p, l // batch, batch), (y.shape, mask.shape)
    steps = l // batch
    kern = functools.partial(_kernel, lam=lam, lr=lr, batch=batch)
    return pl.pallas_call(
        kern,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, l, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, steps, batch), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, steps, batch), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((p, 1, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x, y, mask)
