"""Compile the Pallas kernels for a described TPU v5e (no chip attached).

Interpret mode cannot see what the chip's compiler refuses: block shapes
that break the (8, 128) tiling rule, or more VMEM than a kernel may use.
Compiling here for a v5e described by ``jax.experimental.topologies``
catches both at no chip time.  Each case lowers with ``interpret=False`` at
the serving shapes (bf16, head dim 128, 8 query heads per KV head, 4096
KV positions) or the paper's analytics shapes, checks that the Pallas
kernel is in the compiled program, and keeps JAX's persistent compile
cache off: a TPU executable written here could not be read back on CPU.

This is the only file that describes the topology, and it does so inside
a fixture, so that every pytest worker collects the same tests and only
the one that runs this file loads the TPU compiler.
"""
import importlib
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

T, HD, KV, G = 4096, 128, 8, 8          # deepseek-67b attention widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel compiled"
    return compiled


def _decode(q, k, v, pos):
    from repro.kernels.decode_attention import ops

    return ops.decode_attention(q, k, v, pos=pos, interpret=False)


def _extend(q, k, v, t_real):
    from repro.kernels.extend_attention import ops

    return ops.extend_attention(q, k, v, t_real=t_real, interpret=False)


def _zt_z(z):
    from repro.kernels.linreg_stats.kernel import zt_z

    return zt_z(z, block_n=512, interpret=False)


def _grouped_stats(x, y):
    from repro.kernels.nb_stats.kernel import grouped_stats

    return grouped_stats(x, y, n_classes_padded=8, block_n=512,
                         interpret=False)


def _sgd_chunks(x, y, mask):
    from repro.kernels.logreg_sgd.kernel import sgd_chunks

    return sgd_chunks(x, y, mask, lam=1e-3, lr=0.5, batch=64, interpret=False)


def _dequant(q, scales):
    from repro.kernels.quant_kv.kernel import dequant_blocks_streams

    return dequant_blocks_streams(q, scales, interpret=False)


BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
CASES = {
    # 4 sequences x 8 KV heads = 32 streams, one query row each
    "decode_attention": (_decode, ((4, 1, KV * G, HD), BF16),
                         ((4, T, KV, HD), BF16), ((4, T, KV, HD), BF16),
                         ((4,), I32)),
    # one sequence, a 128-token chunk at the end of T positions
    "extend_attention": (_extend, ((1, 128, KV * G, HD), BF16),
                         ((1, T, KV, HD), BF16), ((1, T, KV, HD), BF16),
                         ((), I32)),
    # one sequence, a 512-token grouped pass (four q tiles of 128) at the
    # end of a 3840-position serving slot: VMEM as at 128 tokens
    "extend_attention_512": (_extend, ((1, 512, KV * G, HD), BF16),
                             ((1, 3840, KV, HD), BF16),
                             ((1, 3840, KV, HD), BF16), ((), I32)),
    # the paper's 5M rows, [X | y] padded to one lane tile
    "linreg_zt_z": (_zt_z, ((5_000_192, 128), F32)),
    "nb_grouped_stats": (_grouped_stats, ((1_000_448, 128), F32),
                         ((1_000_448, 1), I32)),
    # 4 chunks of 8192 rows, 64-row minibatches
    "logreg_sgd_chunks": (_sgd_chunks, ((4, 8192, 128), F32),
                          ((4, 128, 64), F32), ((4, 128, 64), F32)),
    # a 2176-token, 4-layer deepseek-67b segment in 128-row scale blocks
    "quant_dequant": (_dequant, ((4 * 17 * KV * 2, 128, HD), I8),
                      ((4 * 17 * KV * 2,), F32)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, *shapes = CASES[name]
    compiled = _compile(fn, one_chip, *shapes)
    mem = compiled.memory_analysis()
    assert mem is not None and mem.argument_size_in_bytes > 0


#: the statistics wrappers' jitted programs, which take each scan's packed
#: buffer flat and reshape, lane-pad and slice around the kernel
WRAPPERS = {
    # a 65,536-row bucket of [X | y], 10 features
    "linreg_gram": ("repro.kernels.linreg_stats.ops",
                    lambda m, f: m._gram(f, w=11, block_n=512), 65_536 * 11),
    # the same bucket of [X | label], 2 classes
    "nb_grouped": ("repro.kernels.nb_stats.ops",
                   lambda m, f: m._grouped(f, w=11, n_classes=2, block_n=512),
                   65_536 * 11),
    # 8 chunk slots of 10,048 rows of [X | y | mask]
    "logreg_sgd_chunks": ("repro.kernels.logreg_sgd.ops",
                          lambda m, f: m._sgd(f, shape=(8, 10_048, 12), lam=1e-3,
                                              lr=0.5, batch=64),
                          8 * 10_048 * 12),
}


@pytest.mark.parametrize("name", sorted(WRAPPERS))
def test_wrapper_program_compiles_for_v5e(name, one_chip, no_compile_cache,
                                          monkeypatch):
    mod, call, size = WRAPPERS[name]
    ops = importlib.import_module(mod)
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    try:
        _compile(lambda f: call(ops, f), one_chip, ((size,), F32))
    finally:
        jax.clear_caches()      # no program traced for the chip stays cached
