"""Per-class grouped statistics as one-hot matmuls — the MXU does GROUP BY.

The 2015 system computed NB counters with SQL aggregation; the TPU-native
formulation builds a one-hot class matrix per row block and hits the MXU
with ``onehotᵀ @ [1 | X | X²]`` — counts, sums and squared sums land in one
``(C, 1+2d)`` accumulator, again touching X exactly once.

Tiling: grid over row blocks.  Per step the kernel materializes the one-hot
block in VMEM (block_n × C), squares X on the VPU, and issues a single
``(C × block_n) @ (block_n × (1+2d))`` MXU op into the revisited fp32
accumulator block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: full f32 MXU passes: by default Mosaic rounds f32 operands to bf16 for
#: a single pass, which would cost the statistics ~3 significant digits
_F32 = jax.lax.Precision.HIGHEST


def _kernel(x_ref, y_ref, out_ref, *, n_classes_padded: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # (bn, dp)
    yv = y_ref[...]                              # (bn, 1) int32; −1 = padding row
    bn = x.shape[0]
    classes = jax.lax.broadcasted_iota(jnp.int32, (bn, n_classes_padded), 1)
    onehot = (classes == yv).astype(jnp.float32)  # padding rows match nothing
    ones = jnp.ones((bn, 1), jnp.float32) * (yv >= 0).astype(jnp.float32)
    g = jnp.concatenate([ones, x, x * x], axis=1)  # (bn, 1 + 2·dp)
    out_ref[...] += jax.lax.dot_general(
        onehot, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_F32,
    )


@functools.partial(jax.jit, static_argnames=("n_classes_padded", "block_n", "interpret"))
def grouped_stats(x, y, *, n_classes_padded: int, block_n: int = 512, interpret: bool = False):
    """Accumulate ``onehot(y)ᵀ @ [1 | x | x²]`` over row blocks.

    ``x`` (n, dp) pre-padded, ``y`` (n, 1) int32 with −1 marking padding rows.
    Returns ``(Cp, 1 + 2·dp)`` fp32.
    """
    n, dp = x.shape
    assert n % block_n == 0 and dp % 128 == 0
    width = 1 + 2 * dp
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_kernel, n_classes_padded=n_classes_padded),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, dp), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_classes_padded, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_classes_padded, width), jnp.float32),
        interpret=interpret,
    )(x, y)
