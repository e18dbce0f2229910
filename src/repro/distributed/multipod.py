"""Multi-pod train step: hierarchical gradient exchange with EF-int8
compression on the pod axis.

Cross-pod links (DCN) are ~an order of magnitude slower than intra-pod ICI,
so the pod axis must not carry fp32 gradients.  Structure:

  * ``shard_map`` over the **pod** axis only (``data``/``model`` stay in
    auto mode — the inner step partitions exactly like the single-pod one);
  * each pod computes gradients for its batch shard (intra-pod collectives
    unchanged);
  * the pod-axis all-reduce runs on **error-feedback int8** payloads
    (8× less DCN traffic; the EF residual rides in the optimizer-adjacent
    state so quantization bias cannot accumulate).

``make_multipod_train_step`` returns
``(params, opt_state, ef_state, batch, step) → (params, opt_state, ef_state,
metrics)``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.train.optim import Optimizer, clip_by_global_norm, make_optimizer, warmup_cosine

from .compression import compressed_psum, ef_state_like


def make_multipod_train_step(
    model,
    mesh: Mesh,
    optimizer: Optional[Optimizer] = None,
    *,
    schedule: Optional[Callable] = None,
    microbatches: Optional[int] = None,
    max_grad_norm: float = 1.0,
    compress: bool = True,
):
    assert "pod" in mesh.axis_names, "multi-pod step needs a 'pod' mesh axis"
    cfg = model.cfg
    opt = optimizer if optimizer is not None else make_optimizer(cfg.optimizer)
    sched = schedule if schedule is not None else warmup_cosine(3e-4, 200, 10_000)
    k = microbatches if microbatches is not None else cfg.train_microbatches

    def per_pod_step(params, opt_state, ef, batch, step):
        # grads over this pod's batch shard (mean over local microbatches)
        def accum(carry, mb):
            gsum, lsum = carry
            (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(params, mb)
            return (jax.tree.map(lambda a, g: a + g.astype(a.dtype), gsum, grads),
                    lsum + loss), None

        mbs = {kk: v.reshape(k, v.shape[0] // k, *v.shape[1:]) for kk, v in batch.items()}
        gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = jax.lax.scan(accum, (gzero, jnp.float32(0.0)), mbs)
        grads = jax.tree.map(lambda g: g / k, gsum)

        # cross-pod exchange (the only traffic on DCN)
        if compress:
            grads, ef = compressed_psum(grads, ef, "pod")
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"), grads)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = sched(step)
        new_params, new_opt = opt.update(grads, opt_state, params, lr)
        loss = jax.lax.pmean(lsum / k, "pod")
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_opt, ef, metrics

    # pod axis manual; data/model remain auto so the inner step lowers with
    # the same shardings as single-pod. params/opt/ef are pod-replicated;
    # the batch's leading dim is split across pods.
    step_fn = jax.shard_map(
        per_pod_step,
        mesh=mesh,
        in_specs=(P(), P(), P(), P("pod"), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
        axis_names={"pod"},
    )
    return step_fn, opt


def ef_init(params):
    return ef_state_like(params)
