"""Fused sufficient-statistics kernel: one HBM pass → XᵀX, Xᵀy (and yᵀy).

TPU adaptation of the paper's §3.1.1 scan.  Trick: augment ``Z = [X | y]``;
then a single rank-``block_n`` MXU update ``ZᵀZ`` yields ``A`` in the top-
left ``d×d`` block, ``B`` in column ``d``, and ``yᵀy`` (the SSE building
block the paper mentions for ANOVA/AIC maintenance) at ``[d, d]`` — three
statistics for the price of one matmul, with X touched exactly once.

Tiling: grid over row-blocks; ``Z`` tiles of ``(block_n, dp)`` stream
HBM→VMEM; the ``(dp, dp)`` fp32 accumulator lives in the revisited output
block.  ``dp`` is padded to a lane multiple (128) and ``block_n`` to a
sublane multiple so the MXU sees aligned operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: full f32 MXU passes: by default Mosaic rounds f32 operands to bf16 for
#: a single pass, which would cost the statistics ~3 significant digits
_F32 = jax.lax.Precision.HIGHEST


def _kernel(z_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...].astype(jnp.float32)
    # rank-block_n update: (dp, block_n) @ (block_n, dp) on the MXU
    out_ref[...] += jax.lax.dot_general(
        z, z, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_F32,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def zt_z(z: jnp.ndarray, *, block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """``zᵀz`` over row blocks; ``z`` must be pre-padded to multiples."""
    n, dp = z.shape
    assert n % block_n == 0 and dp % 128 == 0, (n, dp)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, dp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((dp, dp), jnp.float32),
        interpret=interpret,
    )(z)
