"""Plain reference of a dense Llama-architecture decoder (DeepSeek LLM 67B),
and the seeded weights both it and the served program run on.

It follows the published description (arXiv:2401.02954: pre-norm RMSNorm,
grouped-query attention with rotary position embedding in the Llama
"rotate half" layout, SwiGLU feed-forward, an untied output head) in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, no batching.  It imports nothing of the program.

Weights are drawn here from the seed, leaf by leaf and layer by layer, in
the dtype they are served in: ``layer_weights(key, cfg, i)`` gives layer
``i`` alone, so the reference regenerates one layer at a time (float32
weights of the whole model would not fit the chip) and the benchmark
stacks the same draws for the program.

``forward_logits`` runs teacher forcing: every sequence (prompt plus the
tokens the program served) through the model one layer at a time, with
query blocks in attention, and returns the logits at the positions asked
for.  With ``fp8`` every matrix product's operands are first rounded to
per-tensor scaled float8_e4m3fn: the control, the precision below bf16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

def dims(cfg: dict) -> dict:
    return {"d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"])}


def _shapes(cfg: dict) -> dict:
    n = dims(cfg)
    d, H, KV, hd, F = n["d"], n["H"], n["KV"], n["hd"], n["F"]
    return {"ln1": (d,), "wq": (d, H, hd), "wk": (d, KV, hd), "wv": (d, KV, hd),
            "wo": (H, hd, d), "ln2": (d,), "w_gate": (d, F), "w_up": (d, F),
            "w_down": (F, d)}


def _draw(key, shape, std: float, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def root_key(seed: int):
    """The weights' key: any whole-number seed, folded to 32 bits."""
    s = int(np.random.SeedSequence([int(seed), 11]).generate_state(1)[0])
    return jax.random.key(s)


def layer_weights(key, cfg: dict, i, dtype=jnp.bfloat16) -> dict:
    """Layer ``i``'s weights: norms at one, projections normal with the
    configuration's ``init`` standard deviations (the residual-stream
    outputs ``wo`` and ``w_down`` at ``residual_out_std``)."""
    init = cfg["init"]
    kl = jax.random.fold_in(key, 1000 + i)
    out = {}
    for j, (name, shape) in enumerate(_shapes(cfg).items()):
        if name in ("ln1", "ln2"):
            out[name] = jnp.ones(shape, dtype)
            continue
        std = init["residual_out_std"] if name in ("wo", "w_down") else init["std"]
        out[name] = _draw(jax.random.fold_in(kl, j), shape, std, dtype)
    return out


def embed_weights(key, cfg: dict, dtype=jnp.bfloat16):
    n = dims(cfg)
    return _draw(jax.random.fold_in(key, 1), (n["V"], n["d"]),
                 cfg["init"]["embed_std"], dtype)


def head_weights(key, cfg: dict, dtype=jnp.bfloat16):
    n = dims(cfg)
    return _draw(jax.random.fold_in(key, 2), (n["d"], n["V"]),
                 cfg["init"]["std"], dtype)


# -- the forward pass --------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotary embedding, Llama layout: the first and second halves of each
    head are the two coordinates of each rotated pair."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq            # (T, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _fp8(x):
    """``x`` through float8_e4m3fn as an fp8 matmul takes it: scaled so its
    largest magnitude sits at the format's largest (448), rounded, and
    scaled back."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    # the barrier keeps the compiler from folding the round trip away
    q = jax.lax.optimization_barrier((x * scale).astype(jnp.float8_e4m3fn))
    return q.astype(jnp.float32) / scale


def _mm(spec, a, b, fp8):
    """A float32 product; with ``fp8`` its operands are first rounded to
    per-tensor scaled float8_e4m3fn."""
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b)


def _layer(x, w, *, eps, theta, q_block, fp8):
    """One decoder layer over one sequence x (T, d), float32."""
    T = x.shape[0]
    H, hd = w["wq"].shape[1], w["wq"].shape[2]
    KV = w["wk"].shape[1]
    G = H // KV
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm("td,dhk->thk", h, w["wq"], fp8), pos, theta)
    k = _rope(_mm("td,dhk->thk", h, w["wk"], fp8), pos, theta)
    v = _mm("td,dhk->thk", h, w["wv"], fp8)
    k = jnp.repeat(k, G, axis=1)                              # (T, H, hd)
    v = jnp.repeat(v, G, axis=1)
    nq = T // q_block

    def block(_, i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        s = _mm("qhk,thk->hqt", qb, k, fp8) * hd ** -0.5
        qp = i * q_block + jnp.arange(q_block)
        s = jnp.where(qp[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return None, _mm("hqt,thk->qhk", p, v, fp8)

    _, att = jax.lax.scan(block, None, jnp.arange(nq))
    att = att.reshape(T, H, hd)
    x = x + _mm("thk,hkd->td", att, w["wo"], fp8)
    h = _rms(x, w["ln2"], eps)
    f = jax.nn.silu(_mm("td,df->tf", h, w["w_gate"], fp8)) * \
        _mm("td,df->tf", h, w["w_up"], fp8)
    return x + _mm("tf,fd->td", f, w["w_down"], fp8)


def forward_logits(seed: int, cfg: dict, seqs: np.ndarray, want: list,
                   *, fp8: bool = False, q_block: int = 512) -> list:
    """Logits (float32, numpy) of ``seqs`` (R, T) at positions ``want[r]``.

    Teacher forcing: row r is a prompt followed by the served tokens; the
    logits at position p predict token p + 1.  Runs one layer at a time
    over all rows, each layer's weights drawn anew from the seed.
    """
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    key = root_key(seed)
    T = seqs.shape[1]
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        # weights leave their jitted draw in bf16 and widen outside it: inside
        # one program the compiler may skip the bf16 rounding
        emb = embed_weights(key, cfg).astype(f32)
        xs = [emb[jnp.asarray(s)] for s in seqs]
        del emb
        layer = jax.jit(lambda x, w: _layer(x, w, eps=eps, theta=theta,
                                            q_block=qb, fp8=fp8))
        draw = jax.jit(lambda k, i: layer_weights(k, cfg, i))
        for i in range(dims(cfg)["L"]):
            w = jax.tree.map(lambda a: a.astype(f32), draw(key, i))
            xs = [layer(x, w) for x in xs]
            del w
        head = head_weights(key, cfg).astype(f32)
        out = []
        for x, ps in zip(xs, want):
            hsel = _rms(x[jnp.asarray(ps)], 1.0, eps)
            out.append(np.asarray(_mm("td,dv->tv", hsel, head, fp8)))
        return out
