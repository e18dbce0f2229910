"""Language-model assembly: heterogeneous layer stacks as scanned segments.

A config is compiled into **segments**: ``(period, n_periods)`` where
``period`` is a tuple of LayerSpecs (e.g. Jamba's 8-layer SSD/attn/MoE
interleave).  Each segment scans over periods with stacked parameters —
HLO stays one-period-sized regardless of depth, which keeps the 512-way
SPMD dry-run compile tractable for 96-layer archs.

All forward paths thread an activation-sharding hook
(:func:`repro.distributed.sharding.constrain`) so the distribution layer
owns layout decisions without the model knowing mesh details.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain

from . import attention as attn
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssd as ssd_mod
from .common import (CACHE_STATE_KEYS, ParamSpec, cache_leaf_key, dense,
                     init_params, rms_norm, spec_map)


@dataclass(frozen=True)
class LayerSpec:
    mixer: str           # attn | attn_bidir | mla | ssd
    mlp: str             # dense | moe | none
    cross: bool = False  # add a cross-attention sublayer


def build_segments(cfg: ArchConfig) -> list[tuple[tuple[LayerSpec, ...], int]]:
    L = cfg.n_layers
    mixer = "mla" if cfg.mla is not None else "attn"

    def mlp_kind(idx: int) -> str:
        if cfg.d_ff == 0 and cfg.moe is None:
            return "none"
        if cfg.moe is None:
            return "dense"
        m = cfg.moe
        if idx < m.first_dense_layers:
            return "dense"
        if m.every > 1 and idx % m.every != m.every - 1:
            return "dense"
        return "moe"

    if cfg.family == "ssm":
        return [((LayerSpec("ssd", "none"),), L)]
    if cfg.family == "hybrid":
        P = cfg.hybrid_period
        period = tuple(
            LayerSpec("attn" if i == cfg.hybrid_attn_idx else "ssd", mlp_kind(i))
            for i in range(P)
        )
        assert L % P == 0
        return [(period, L // P)]
    if cfg.family == "vlm":
        E = cfg.cross_attn_every
        period = tuple(
            LayerSpec("attn", "dense", cross=(i == E - 1)) for i in range(E)
        )
        assert L % E == 0
        return [(period, L // E)]
    if cfg.family == "encdec":
        return [((LayerSpec("attn", "dense", cross=True),), L)]
    # dense / moe decoders, with optional leading dense layers
    segs: list[tuple[tuple[LayerSpec, ...], int]] = []
    kinds = [mlp_kind(i) for i in range(L)]
    i = 0
    while i < L:
        j = i
        while j < L and kinds[j] == kinds[i]:
            j += 1
        segs.append(((LayerSpec(mixer, kinds[i]),), j - i))
        i = j
    return segs


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _attn_specs(cfg: ArchConfig) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", None)),
        "wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", None)),
        "wo": ParamSpec((H, hd, d), ("heads", None, "embed"), scale=cfg.n_layers ** -0.5),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), (None,), "ones")
        s["k_norm"] = ParamSpec((hd,), (None,), "ones")
    return s


def _mla_specs(cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    return {
        "w_dq": ParamSpec((d, m.q_lora_rank), ("embed", None)),
        "q_norm": ParamSpec((m.q_lora_rank,), (None,), "ones"),
        "w_uq": ParamSpec((m.q_lora_rank, H, m.qk_nope_head_dim + m.qk_rope_head_dim),
                          (None, "heads", None)),
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamSpec((m.kv_lora_rank,), (None,), "ones"),
        "w_uk": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim), (None, "heads", None)),
        "w_uv": ParamSpec((m.kv_lora_rank, H, m.v_head_dim), (None, "heads", None)),
        "w_o": ParamSpec((H, m.v_head_dim, d), ("heads", None, "embed"),
                         scale=cfg.n_layers ** -0.5),
    }


def _ssd_specs(cfg: ArchConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.d_inner(d)
    h = s.n_heads(d)
    gn = s.n_groups * s.d_state
    conv_ch = d_in + 2 * gn
    return {
        "w_in": ParamSpec((d, 2 * d_in + 2 * gn + h), ("embed", "ssm_inner")),
        "conv_w": ParamSpec((s.conv_width, conv_ch), (None, "ssm_inner")),
        "conv_b": ParamSpec((conv_ch,), ("ssm_inner",), "zeros"),
        "a_log": ParamSpec((h,), ("ssm_heads",), "ones"),
        "d_skip": ParamSpec((h,), ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec((h,), ("ssm_heads",), "zeros"),
        "out_norm": ParamSpec((d_in,), ("ssm_inner",), "ones"),
        "w_out": ParamSpec((d_in, d), ("ssm_inner", "embed"), scale=cfg.n_layers ** -0.5),
    }


def _dense_mlp_specs(cfg: ArchConfig, d_ff: int) -> dict:
    d = cfg.d_model
    s = {
        "w_up": ParamSpec((d, d_ff), ("embed", "ff")),
        "w_down": ParamSpec((d_ff, d), ("ff", "embed"), scale=cfg.n_layers ** -0.5),
    }
    if cfg.activation == "swiglu":
        s["w_gate"] = ParamSpec((d, d_ff), ("embed", "ff"))
    return s


def _moe_specs(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    s = {
        "router": ParamSpec((d, m.n_experts), ("embed", None)),
        "experts": {
            "w_gate": ParamSpec((m.n_experts, d, m.d_ff_expert), ("experts", "embed", "ff")),
            "w_up": ParamSpec((m.n_experts, d, m.d_ff_expert), ("experts", "embed", "ff")),
            "w_down": ParamSpec((m.n_experts, m.d_ff_expert, d), ("experts", "ff", "embed"),
                                scale=cfg.n_layers ** -0.5),
        },
    }
    if m.n_shared:
        dsh = (m.d_ff_shared or m.d_ff_expert) * m.n_shared
        s["shared"] = _dense_mlp_specs(cfg, dsh)
    return s


def _layer_specs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    out: dict = {"ln1": ParamSpec((d,), ("embed",), "ones")}
    if spec.mixer in ("attn", "attn_bidir"):
        out["mixer"] = _attn_specs(cfg)
    elif spec.mixer == "mla":
        out["mixer"] = _mla_specs(cfg)
    elif spec.mixer == "ssd":
        out["mixer"] = _ssd_specs(cfg)
    else:
        raise KeyError(spec.mixer)
    if spec.cross:
        out["cross_ln"] = ParamSpec((d,), ("embed",), "ones")
        out["cross"] = _attn_specs(cfg)
    if spec.mlp != "none":
        out["ln2"] = ParamSpec((d,), ("embed",), "ones")
        out["mlp"] = _moe_specs(cfg) if spec.mlp == "moe" else _dense_mlp_specs(cfg, cfg.d_ff)
    return out


def _stack_specs(tree, n: int):
    return spec_map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale), tree
    )


def param_specs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    specs: dict = {
        "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"), scale=1.0),
        "final_norm": ParamSpec((d,), ("embed",), "ones"),
        "segments": [
            _stack_specs({f"p{j}": _layer_specs(cfg, ls) for j, ls in enumerate(period)}, n)
            for period, n in build_segments(cfg)
        ],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, cfg.vocab_size), ("embed", "vocab"))
    if cfg.encoder_layers:
        enc_period = (LayerSpec("attn_bidir", "dense"),)
        specs["encoder"] = {
            "layers": _stack_specs(
                {"p0": _layer_specs(cfg, enc_period[0])}, cfg.encoder_layers
            ),
            "final_norm": ParamSpec((d,), ("embed",), "ones"),
        }
    if cfg.vision_context:
        specs["vision_proj"] = ParamSpec((d, d), ("embed", None))
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _as_attn_params(p: dict) -> attn.AttnParams:
    return attn.AttnParams(p["wq"], p["wk"], p["wv"], p["wo"],
                           p.get("q_norm"), p.get("k_norm"))


def _as_mla_params(p: dict) -> mla_mod.MLAParams:
    return mla_mod.MLAParams(p["w_dq"], p["q_norm"], p["w_uq"], p["w_dkv"],
                             p["kv_norm"], p["w_uk"], p["w_uv"], p["w_o"])


def _as_ssd_params(p: dict) -> ssd_mod.SSDParams:
    return ssd_mod.SSDParams(p["w_in"], p["conv_w"], p["conv_b"], p["a_log"],
                             p["d_skip"], p["dt_bias"], p["out_norm"], p["w_out"])


def _as_moe_params(p: dict) -> moe_mod.MoEParams:
    shared = None
    if "shared" in p:
        sh = p["shared"]
        shared = (sh["w_gate"], sh["w_up"], sh["w_down"])
    e = p["experts"]
    return moe_mod.MoEParams(
        p["router"], moe_mod.ExpertParams(e["w_gate"], e["w_up"], e["w_down"]), shared
    )


class LM:
    """Decoder LM (plus optional encoder / vision context) for one ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.segments = build_segments(cfg)
        self.specs = param_specs(cfg)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # -- params ----------------------------------------------------------
    def init(self, key) -> dict:
        return init_params(self.specs, key, self.param_dtype)

    # -- pieces ------------------------------------------------------------
    def _mixer(self, spec: LayerSpec, p, x, positions, ctx_kv, want_state=False):
        cfg = self.cfg
        if spec.mixer == "ssd":
            if want_state:
                out, st = ssd_mod.ssd_block(_as_ssd_params(p), cfg.ssm, cfg.d_model,
                                            x, norm_eps=cfg.norm_eps,
                                            return_state=True)
                return out, st
            return ssd_mod.ssd_block(_as_ssd_params(p), cfg.ssm, cfg.d_model, x,
                                     norm_eps=cfg.norm_eps), None
        if spec.mixer == "mla":
            out, kv = mla_mod.mla_self_attention(
                _as_mla_params(p), cfg.mla, x, positions, theta=cfg.rope_theta,
                block=cfg.attn_block)
            return out, kv
        causal = spec.mixer != "attn_bidir"
        out, kv = attn.self_attention(
            _as_attn_params(p), x, positions, causal=causal, theta=cfg.rope_theta,
            expand_kv=cfg.expand_kv, block=cfg.attn_block)
        return out, kv

    def _layer(self, spec: LayerSpec, p, x, positions, ctx_kv, aux, want_state=False):
        cfg = self.cfg
        h = x.astype(self.compute_dtype)
        mixed, kv = self._mixer(spec, p["mixer"], rms_norm(h, p["ln1"], cfg.norm_eps),
                                positions, ctx_kv, want_state)
        x = x + mixed.astype(x.dtype)
        if spec.cross:
            ck = attn.project_context(_as_attn_params(p["cross"]), ctx_kv)
            xc = attn.cross_attention(
                _as_attn_params(p["cross"]),
                rms_norm(x.astype(self.compute_dtype), p["cross_ln"], cfg.norm_eps), ck)
            x = x + xc.astype(x.dtype)
        if spec.mlp != "none":
            hn = rms_norm(x.astype(self.compute_dtype), p["ln2"], cfg.norm_eps)
            if spec.mlp == "moe":
                y, a = moe_mod.moe_ffn(_as_moe_params(p["mlp"]), cfg.moe, hn,
                                       activation=cfg.activation,
                                       groups=cfg.moe_groups)
                aux = aux + a
            else:
                y = moe_mod.dense_ffn(p["mlp"], hn, cfg.activation)
            x = x + y.astype(x.dtype)
        x = constrain(x, "batch", "seq", None)
        return x, kv, aux

    def _run_segment(self, period, seg_params, x, positions, ctx, remat: bool):
        """Scan one segment; returns (x, aux)."""

        def body(carry, xs):
            x, aux = carry
            for j, spec in enumerate(period):
                x, _, aux = self._layer(spec, xs[f"p{j}"], x, positions, ctx, aux)
            return (x, aux), None

        if remat:
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if self.cfg.remat == "dots_saveable"
                else None
            )
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), seg_params)
        return x, aux

    # -- encoder / context --------------------------------------------------
    def _context(self, params, batch):
        cfg = self.cfg
        if cfg.encoder_layers:
            enc = params["encoder"]
            x = batch["enc_feats"].astype(self.compute_dtype)
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
            spec = LayerSpec("attn_bidir", "dense")

            def body(carry, xs):
                h, aux = carry
                h, _, aux = self._layer(spec, xs["p0"], h, pos, None, aux)
                return (h, aux), None

            (x, _), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), enc["layers"])
            return rms_norm(x, enc["final_norm"], cfg.norm_eps)
        if cfg.vision_context:
            return dense(batch["image_embeds"].astype(self.compute_dtype),
                         params["vision_proj"])
        return None

    # -- public entry points --------------------------------------------------
    def forward(self, params, batch, *, remat: Optional[bool] = None):
        """tokens (B,S) → final hidden states (B,S,d), aux loss."""
        cfg = self.cfg
        remat = (cfg.remat != "none") if remat is None else remat
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = params["embed"].astype(self.compute_dtype)[tokens]
        x = constrain(x, "batch", None, None)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        ctx = self._context(params, batch)
        aux = jnp.float32(0.0)
        for (period, n), seg_params in zip(self.segments, params["segments"]):
            x, a = self._run_segment(period, seg_params, x, positions, ctx, remat)
            aux = aux + a
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    def logits(self, params, hidden):
        head = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        out = jnp.einsum("bsd,dv->bsv", hidden.astype(self.compute_dtype),
                         head.astype(self.compute_dtype))
        return constrain(out, "batch", None, "vocab")

    def loss_fn(self, params, batch):
        """Mean next-token CE (+ MoE aux).  Optionally chunked over sequence."""
        cfg = self.cfg
        hidden, aux = self.forward(params, batch)
        targets = batch["targets"]
        if cfg.logit_chunk and hidden.shape[1] % cfg.logit_chunk == 0:
            nchunk = hidden.shape[1] // cfg.logit_chunk
            hs = hidden.reshape(hidden.shape[0], nchunk, cfg.logit_chunk, -1)
            ts = targets.reshape(targets.shape[0], nchunk, cfg.logit_chunk)

            def chunk_loss(carry, xs):
                h, t = xs                       # (B, chunk, d), (B, chunk)
                ll = _token_ce(self.logits(params, h), t)
                return carry + ll.sum(), None

            total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0),
                                    (hs.swapaxes(0, 1), ts.swapaxes(0, 1)))
            ce = total / targets.size
        else:
            ce = _token_ce(self.logits(params, hidden), targets).mean()
        moe_w = cfg.moe.router_aux_weight if cfg.moe else 0.0
        return ce + moe_w * aux, {"ce": ce, "aux": aux}

    # -- serving ------------------------------------------------------------
    def prefill(self, params, batch):
        """Returns (last-position logits (B,V), cache tree)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = params["embed"].astype(self.compute_dtype)[tokens]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        ctx = self._context(params, batch)
        caches: list = []
        for (period, n), seg_params in zip(self.segments, params["segments"]):
            def body(x, xs):
                new_caches = {}
                for j, spec in enumerate(period):
                    x, kv, _ = self._layer(spec, xs[f"p{j}"], x, positions, ctx,
                                           jnp.float32(0.0), want_state=True)
                    new_caches[f"p{j}"] = self._prefill_cache(spec, xs[f"p{j}"], kv, ctx)
                return x, new_caches

            x, seg_cache = jax.lax.scan(body, x, seg_params)
            caches.append(seg_cache)
        hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.logits(params, hidden[:, -1:, :])[:, 0]
        return logits, caches

    def _prefill_cache(self, spec: LayerSpec, p, kv, ctx):
        entry = {}
        if spec.mixer in ("attn", "attn_bidir"):
            entry["k"], entry["v"] = kv
        elif spec.mixer == "mla":
            entry["c_kv"], entry["k_rope"] = kv
        elif spec.mixer == "ssd":
            entry["conv"], entry["ssm"] = kv
        if spec.cross:
            ck, cv = attn.project_context(_as_attn_params(p["cross"]), ctx)
            entry["ck"], entry["cv"] = ck, cv
        return entry

    def prefill_extend(self, params, caches, tokens, start):
        """Extend a capacity-padded cache with a block of tokens.

        The serving engine's gap-filler: given caches whose sequence axis
        is padded to some capacity ``cap`` and holds valid state for
        [0, start), process ``tokens`` (B, nb) at positions
        [start, start+nb) — writing their KV in place — and return
        (last-position logits, caches of the same capacity now valid to
        start+nb).  ``start`` is a *traced* int32 scalar, so one compiled
        executable per (cap, nb) serves every chunk of every request;
        positions ≥ start+nb hold garbage that the causal mask excludes.
        SSD layers resume from their final (conv, ssm) states;
        attention/MLA layers attend over prefix+block.  ``cap`` must be
        ≥ start+nb (the caller buckets it).
        """
        cfg = self.cfg
        b, nb = tokens.shape
        x = params["embed"].astype(self.compute_dtype)[tokens]
        positions = start + jnp.broadcast_to(jnp.arange(nb)[None], (b, nb))
        # cross-attention context K/V comes from the cache (ck/cv), so the
        # modality frontend is never re-run on the extend path
        new_caches: list = []
        for (period, n), seg_params, seg_cache in zip(
            self.segments, params["segments"], caches
        ):
            def body(x, xs):
                p, cache = xs
                out_cache = {}
                for j, spec in enumerate(period):
                    x, out_cache[f"p{j}"] = self._extend_layer(
                        spec, p[f"p{j}"], cache[f"p{j}"], x, positions, start)
                return x, out_cache

            x, seg_cache_new = jax.lax.scan(body, x, (seg_params, seg_cache))
            new_caches.append(seg_cache_new)
        hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.logits(params, hidden[:, -1:, :])[:, 0]
        return logits, new_caches

    def prefill_extend_many(self, params, caches, tokens, start, n_chunks):
        """Fused multi-chunk extend: one dispatch fills a whole plan gap.

        tokens (B, n_slots, chunk) is a fixed-slot chunk buffer; slots
        i < ``n_chunks`` (traced) hold real document chunks starting at
        ``start + i·chunk``, later slots are padding and never touched —
        the loop is a dynamic-trip-count ``fori_loop``, so the executable
        depends only on (cache capacity, n_slots, chunk) and is shared by
        every gap of every request in the same bucket.

        Returns (logits of the last processed chunk's final position,
        caches, chunk_states) where ``chunk_states`` mirrors the cache
        tree with each running-state leaf ("conv"/"ssm") stacked to
        (n_slots, …) — the state *at the end of each chunk*, which is
        what per-chunk segment materialization needs (a chunk's stored
        SSD state must be the state at its own boundary, not at gap end).
        """
        b, n_slots, chunk = tokens.shape

        def snap_init(path, x):
            if cache_leaf_key(path) in CACHE_STATE_KEYS:
                return jnp.zeros((n_slots,) + x.shape, x.dtype)
            return jnp.zeros((0,), x.dtype)

        def snap_write(i, snap, caches):
            def f(path, s, x):
                if cache_leaf_key(path) in CACHE_STATE_KEYS:
                    idx = (i,) + (0,) * x.ndim
                    return jax.lax.dynamic_update_slice(s, x[None], idx)
                return s
            return jax.tree_util.tree_map_with_path(f, snap, caches)

        def body(i, carry):
            caches, snap, _ = carry
            toks = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)[:, 0]
            logits, caches = self.prefill_extend(params, caches, toks,
                                                 start + i * chunk)
            return (caches, snap_write(i, snap, caches), logits)

        snap0 = jax.tree_util.tree_map_with_path(snap_init, caches)
        logits0 = jnp.zeros((b, self.cfg.vocab_size), self.compute_dtype)
        caches, snap, logits = jax.lax.fori_loop(
            0, n_chunks, body, (caches, snap0, logits0))
        return logits, caches, snap

    def extend_groupable(self) -> bool:
        """Whether ``prefill_extend`` gives each token the same output
        however many tokens share its pass, so that consecutive chunks may
        run as one longer pass.  Not for SSD mixers, whose stored chunks
        need the running state at each chunk's own end, nor for MoE MLPs,
        which drop tokens by a capacity computed per pass."""
        return all(spec.mixer in ("attn", "mla") and spec.mlp != "moe"
                   for period, _ in self.segments for spec in period)

    def _extend_layer(self, spec: LayerSpec, p, cache, x, positions, start):
        cfg = self.cfg
        h = rms_norm(x.astype(self.compute_dtype), p["ln1"], cfg.norm_eps)
        out_cache = dict(cache)
        if spec.mixer == "ssd":
            mixed, st = ssd_mod.ssd_block(
                _as_ssd_params(p["mixer"]), cfg.ssm, cfg.d_model, h,
                norm_eps=cfg.norm_eps, return_state=True,
                initial=(cache["conv"], cache["ssm"]))
            out_cache["conv"], out_cache["ssm"] = st
        elif spec.mixer == "mla":
            mixed, (c_kv, k_rope) = mla_mod.mla_extend(
                _as_mla_params(p["mixer"]), cfg.mla, h, cache["c_kv"],
                cache["k_rope"], positions, start, theta=cfg.rope_theta,
                block=cfg.attn_block)
            out_cache["c_kv"], out_cache["k_rope"] = c_kv, k_rope
        else:
            mixed, (k_full, v_full) = attn.extend_attention_cached(
                _as_attn_params(p["mixer"]), h, cache["k"], cache["v"],
                positions, start, theta=cfg.rope_theta, block=cfg.attn_block)
            out_cache["k"], out_cache["v"] = k_full, v_full
        x = x + mixed.astype(x.dtype)
        if spec.cross:
            xc = attn.cross_attention(
                _as_attn_params(p["cross"]),
                rms_norm(x.astype(self.compute_dtype), p["cross_ln"], cfg.norm_eps),
                (cache["ck"], cache["cv"]))
            x = x + xc.astype(x.dtype)
        if spec.mlp != "none":
            hn = rms_norm(x.astype(self.compute_dtype), p["ln2"], cfg.norm_eps)
            if spec.mlp == "moe":
                y, _ = moe_mod.moe_ffn(_as_moe_params(p["mlp"]), cfg.moe, hn,
                                       activation=cfg.activation,
                                       groups=cfg.moe_groups)
            else:
                y = moe_mod.dense_ffn(p["mlp"], hn, cfg.activation)
            x = x + y.astype(x.dtype)
        return x, out_cache

    def decode_step(self, params, caches, tokens, pos, row_caps=None):
        """One token for every sequence.  tokens (B,1); pos (B,) int32.

        Attention layers route through ``attn.decode_attention``, which
        picks a decode path at trace time (``REPRO_DECODE_KERNEL``): the
        ragged flash-decode Pallas kernel, the blocked-softmax fallback,
        or the legacy dense full-T scores.  Kernel/blocked outputs are
        per-row bit-invariant to the cache's padded capacity, so the
        scheduler may pack mixed-capacity sessions into one decode call.
        MLA and SSD mixers keep their dedicated dense decode paths.

        ``row_caps`` — the pack's static per-row KV capacities in
        non-increasing order — is the scheduler's opt-in to the ragged
        fast path (blocked mode, attention-only stacks): caches update
        in place via per-row scatters instead of the scanned path's full
        O(B·T) cache rewrite per token, and each row's attention stops at
        its own capacity.  Same values either way (scatter vs
        dynamic-update write the same rows; skipped blocks are exact-zero
        no-ops) — it is purely an execution-cost change.
        """
        cfg = self.cfg
        if row_caps is not None and self._ragged_decode_ok():
            return self._decode_step_ragged(params, caches, tokens, pos,
                                            row_caps)
        x = params["embed"].astype(self.compute_dtype)[tokens]
        new_caches: list = []
        for (period, n), seg_params, seg_cache in zip(
            self.segments, params["segments"], caches
        ):
            def body(x, xs):
                p, cache = xs
                out_cache = {}
                for j, spec in enumerate(period):
                    x, out_cache[f"p{j}"] = self._decode_layer(
                        spec, p[f"p{j}"], cache[f"p{j}"], x, pos)
                return x, out_cache

            x, seg_cache_new = jax.lax.scan(body, x, (seg_params, seg_cache))
            new_caches.append(seg_cache_new)
        hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.logits(params, hidden)[:, 0]
        return logits, new_caches

    def _ragged_decode_ok(self) -> bool:
        """Ragged in-place decode serves plain-attention stacks only (the
        blocked tiered path needs k/v leaves; MLA/SSD/cross keep their
        scanned decode)."""
        from repro.kernels.common import decode_kernel_mode

        if decode_kernel_mode() != "blocked":
            return False
        return all(spec.mixer == "attn" and not spec.cross
                   for period, _ in self.segments for spec in period)

    def _decode_step_ragged(self, params, caches, tokens, pos, row_caps):
        """Serving decode over capacity-sorted packs: scan layers with the
        stacked K/V cache in the carry, scatter-writing one token row per
        layer (in place under donation) and reading only the KV blocks
        each row's static capacity reaches.  Value-identical to the
        scanned path; the cost drops from O(B·T_pad) cache traffic per
        token to O(B) writes + O(Σ live KV) reads."""
        cfg = self.cfg
        x = params["embed"].astype(self.compute_dtype)[tokens]
        new_caches: list = []
        for (period, n), seg_params, seg_cache in zip(
            self.segments, params["segments"], caches
        ):
            leaves = tuple((seg_cache[f"p{j}"]["k"], seg_cache[f"p{j}"]["v"])
                           for j in range(len(period)))

            def body(carry, xs):
                x, leaves = carry
                p, i = xs
                out = []
                for j, spec in enumerate(period):
                    k_all, v_all = leaves[j]
                    pj = p[f"p{j}"]
                    h = rms_norm(x.astype(self.compute_dtype), pj["ln1"],
                                 cfg.norm_eps)
                    mixed, k_all, v_all = attn.decode_attention_packed(
                        _as_attn_params(pj["mixer"]), h, k_all, v_all, i,
                        pos, theta=cfg.rope_theta, row_caps=row_caps)
                    x = x + mixed.astype(x.dtype)
                    if spec.mlp != "none":
                        hn = rms_norm(x.astype(self.compute_dtype),
                                      pj["ln2"], cfg.norm_eps)
                        if spec.mlp == "moe":
                            y, _ = moe_mod.moe_ffn(
                                _as_moe_params(pj["mlp"]), cfg.moe, hn,
                                activation=cfg.activation,
                                groups=cfg.moe_groups)
                        else:
                            y = moe_mod.dense_ffn(pj["mlp"], hn,
                                                  cfg.activation)
                        x = x + y.astype(x.dtype)
                    out.append((k_all, v_all))
                return (x, tuple(out)), None

            (x, leaves), _ = jax.lax.scan(
                body, (x, leaves), (seg_params, jnp.arange(n)))
            new_caches.append({
                f"p{j}": {**seg_cache[f"p{j}"],
                          "k": leaves[j][0], "v": leaves[j][1]}
                for j in range(len(period))})
        hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.logits(params, hidden)[:, 0]
        return logits, new_caches

    def _decode_layer(self, spec: LayerSpec, p, cache, x, pos):
        cfg = self.cfg
        h = rms_norm(x.astype(self.compute_dtype), p["ln1"], cfg.norm_eps)
        out_cache = dict(cache)
        if spec.mixer == "ssd":
            mixed, st = ssd_mod.ssd_decode(
                _as_ssd_params(p["mixer"]), cfg.ssm, cfg.d_model, h,
                (cache["conv"], cache["ssm"]), norm_eps=cfg.norm_eps)
            out_cache["conv"], out_cache["ssm"] = st
        elif spec.mixer == "mla":
            mixed, (ckv, krope) = mla_mod.mla_decode(
                _as_mla_params(p["mixer"]), cfg.mla, h, cache["c_kv"],
                cache["k_rope"], pos, theta=cfg.rope_theta)
            out_cache["c_kv"], out_cache["k_rope"] = ckv, krope
        else:
            mixed, (ck, cv) = attn.decode_attention(
                _as_attn_params(p["mixer"]), h, cache["k"], cache["v"], pos,
                theta=cfg.rope_theta)
            out_cache["k"], out_cache["v"] = ck, cv
        x = x + mixed.astype(x.dtype)
        if spec.cross:
            xc = attn.cross_attention(
                _as_attn_params(p["cross"]),
                rms_norm(x.astype(self.compute_dtype), p["cross_ln"], cfg.norm_eps),
                (cache["ck"], cache["cv"]))
            x = x + xc.astype(x.dtype)
        if spec.mlp != "none":
            hn = rms_norm(x.astype(self.compute_dtype), p["ln2"], cfg.norm_eps)
            if spec.mlp == "moe":
                y, _ = moe_mod.moe_ffn(_as_moe_params(p["mlp"]), cfg.moe, hn,
                                       activation=cfg.activation,
                                       groups=cfg.moe_groups)
            else:
                y = moe_mod.dense_ffn(p["mlp"], hn, cfg.activation)
            x = x + y.astype(x.dtype)
        return x, out_cache


def _token_ce(logits, targets):
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return lse - true
