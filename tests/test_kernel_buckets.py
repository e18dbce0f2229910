"""The statistics kernels' wrappers: one host-packed transfer, one jitted
dispatch and one read per scan, at quarter-octave row buckets.

Bucketing adds only zero rows (linreg) or rows of class −1 (naive Bayes),
so the statistics equal those of the rows padded to the next block, to
the bit.  A logreg scan runs all its chunks in one call, the tail chunk
masked past its end; its minibatches with no real row take no step, so
each chunk's weights equal those of its own one-chunk call, to the bit.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.kernels.common import pad_axis, round_up, row_bucket, use_interpret
from repro.kernels.linreg_stats import ops as lr_ops
from repro.kernels.linreg_stats.kernel import zt_z
from repro.kernels.logreg_sgd import ops as lg_ops
from repro.kernels.logreg_sgd.kernel import sgd_chunks
from repro.kernels.logreg_sgd.ref import logreg_sgd_ref
from repro.kernels.nb_stats import ops as nb_ops
from repro.kernels.nb_stats.kernel import grouped_stats

D, BLOCK, CHUNK = 10, 512, 10_000
#: the last length of the ninth quarter-octave, 512·2^(9/4) rows
EDGE = math.floor(512 * 2 ** (9 / 4))
LENGTHS = [1, 511, 512, 513, EDGE - 1, EDGE, EDGE + 1]


@pytest.fixture(autouse=True)
def clean_trace():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, D)).astype(np.float32)
    return X, rng.standard_normal(n).astype(np.float32), rng.integers(0, 3, n)


@pytest.mark.parametrize("n,want", [
    (0, 512), (1, 512), (512, 512), (513, 1024), (1024, 1024), (1025, 1536),
    (EDGE, 2560), (EDGE + 1, 3072), (4096, 4096), (64_379, 65_536),
    (74_499, 78_336)])
def test_row_bucket_values(n, want):
    assert row_bucket(n, BLOCK) == want


@pytest.mark.parametrize("block_n", [8, 256, 512])
def test_row_bucket_bounds(block_n):
    prev = 0
    for n in range(1, 300_000, 997):
        nb = row_bucket(n, block_n)
        assert nb >= n and nb % block_n == 0 and nb >= prev
        if n >= 8 * 512:                    # past the block rounding
            assert nb <= n * 2 ** 0.25 + block_n + 1
        prev = nb


@pytest.mark.parametrize("n", LENGTHS)
def test_linreg_bucketed_equals_unbucketed(n):
    X, y, _ = _data(n)
    Z = np.concatenate([X, y[:, None]], axis=1)
    Z = pad_axis(pad_axis(jnp.asarray(Z), 1, 128), 0, round_up(n, BLOCK))
    want = np.asarray(zt_z(Z, block_n=BLOCK, interpret=use_interpret()))
    got = np.asarray(lr_ops.linreg_gram(X, y, block_n=BLOCK))
    assert got.shape == (D + 1, D + 1)
    assert np.abs(got - want[: D + 1, : D + 1]).max() == 0.0


@pytest.mark.parametrize("n", LENGTHS)
def test_nb_bucketed_equals_unbucketed(n):
    X, _, labels = _data(n)
    npad = round_up(n, BLOCK)
    xp = pad_axis(pad_axis(jnp.asarray(X), 1, 128), 0, npad)
    yp = pad_axis(jnp.asarray(labels, jnp.int32)[:, None], 0, npad, value=-1)
    G = np.asarray(grouped_stats(xp, yp, n_classes_padded=8, block_n=BLOCK,
                                 interpret=use_interpret()))
    want = np.concatenate([G[:3, : 1 + D], G[:3, 129 : 129 + D]], axis=1)
    got = np.asarray(nb_ops.nb_grouped(X, labels, 3, block_n=BLOCK))
    assert got.shape == (3, 1 + 2 * D)
    assert np.abs(got - want).max() == 0.0


@pytest.mark.parametrize("tail", [1, 63, 64, 65, 9_999])
@pytest.mark.parametrize("p", [1, 2, 3])
def test_logreg_scan_equals_per_chunk_calls(p, tail):
    n = (p - 1) * CHUNK + tail
    X, _, labels = _data(n, seed=p)
    y = labels.clip(0, 1).astype(np.float32)
    W = np.asarray(lg_ops.logreg_sgd_chunks(X, y, chunk=CHUNK))
    assert W.shape == (p, D + 1)
    for i, s in enumerate(range(0, n, CHUNK)):
        w = np.asarray(lg_ops.logreg_sgd(X[s : s + CHUNK], y[s : s + CHUNK]))
        np.testing.assert_array_equal(W[i], w)


@pytest.mark.parametrize("masked", [1, 2, 3])
def test_all_masked_minibatch_leaves_weights(masked):
    """Minibatches whose mask is all zero, after real ones, change neither
    ``w`` nor ``b``: the run equals the run without them, to the bit, and
    the reference agrees."""
    batch, steps = 64, 4
    rng = np.random.default_rng(masked)
    x = jnp.asarray(rng.standard_normal((1, steps * batch, 128)), jnp.float32)
    y = jnp.asarray(rng.random((1, steps, batch)) > 0.5, jnp.float32)
    m = jnp.ones((1, steps, batch), jnp.float32).at[:, steps - masked:].set(0.0)
    kw = dict(lam=0.1, lr=0.5, batch=batch, interpret=use_interpret())
    w, b = sgd_chunks(x, y, m, **kw)
    real = (steps - masked) * batch
    w0, b0 = sgd_chunks(x[:, :real], y[:, : steps - masked],
                        m[:, : steps - masked], **kw)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b0))
    assert np.abs(np.asarray(w0)).max() > 0       # real steps moved w
    wr = logreg_sgd_ref(x[0], y.reshape(-1), m.reshape(-1), lam=0.1, lr=0.5,
                        batch=batch)
    wr0 = logreg_sgd_ref(x[0, :real], y.reshape(-1)[:real], m.reshape(-1)[:real],
                         lam=0.1, lr=0.5, batch=batch)
    np.testing.assert_array_equal(np.asarray(wr), np.asarray(wr0))


def test_fully_masked_chunk_stays_zero():
    x = jnp.ones((1, 128, 128), jnp.float32)
    zeros = jnp.zeros((1, 2, 64), jnp.float32)
    w, b = sgd_chunks(x, zeros + 1.0, zeros, lam=0.5, lr=0.5, batch=64,
                      interpret=use_interpret())
    assert not np.asarray(w).any() and not np.asarray(b).any()


@pytest.mark.parametrize("family", ["linreg", "gaussian_nb"])
def test_lengths_in_one_bucket_trace_one_program(family):
    lengths = [EDGE + 1, 2_600, 2_800, 2_896]
    assert {row_bucket(n, BLOCK) for n in lengths} == {3_072}
    fn = lr_ops._gram if family == "linreg" else nb_ops._grouped
    before = fn._cache_size()
    for n in lengths:
        X, y, labels = _data(n)
        if family == "linreg":
            lr_ops.linreg_gram(X, y, block_n=BLOCK).block_until_ready()
        else:
            nb_ops.nb_grouped(X, labels, 3, block_n=BLOCK).block_until_ready()
    assert fn._cache_size() - before <= 1


def test_logreg_scans_trace_one_program_per_chunk_count():
    before = lg_ops._sgd._cache_size()
    for n in (CHUNK + 1, 2 * CHUNK - 7, 2 * CHUNK):     # all p = 2
        X, _, labels = _data(n)
        lg_ops.logreg_sgd_chunks(X, labels.clip(0, 1).astype(np.float32),
                                 chunk=CHUNK).block_until_ready()
    assert lg_ops._sgd._cache_size() - before <= 1


@pytest.mark.parametrize("n", [1, 513, EDGE, 30_001])
@pytest.mark.parametrize("family", ["linreg", "gaussian_nb", "logreg"])
def test_one_call_per_scan_and_padded_rows(family, n):
    from repro.core.families import get_family

    X, y, labels = _data(n)
    params = {"backend": "pallas", "n_classes": 3, "chunk_size": CHUNK}
    target = {"linreg": y, "gaussian_nb": labels,
              "logreg": labels.clip(0, 1).astype(np.float32)}[family]
    trace.enable()
    get_family(family).compute_stats(X, target, params)
    c = trace.summary()["counters"]
    assert c["repro.kernel.calls"] == 1 and c["repro.device_reads"] == 1
    if family == "logreg":
        slots = math.ceil(n / CHUNK) * round_up(CHUNK, 64)
    else:
        slots = row_bucket(n, BLOCK)
    assert c["repro.kernel.rows_padded"] == slots - n
