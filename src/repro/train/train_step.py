"""The jitted training step: loss -> grads -> (optional compression)
-> optimizer update, all inside one XLA program so gradient collectives
overlap with the backward pass (XLA async collectives) and params/opt
state are donated (updated in place).

Gradient accumulation: with ``microbatches=k`` the global batch is split
into k sequential microbatches inside the step (lax.scan); activation
memory scales 1/k while the optimizer still sees the full-batch gradient.
The scan carry (the f32 grad buffer) is not differentiated through, so it
is never stacked.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import loss_fn
from repro.train.optimizer import OptimizerConfig, apply_updates


def make_train_step(cfg: ModelConfig, ocfg: OptimizerConfig,
                    microbatches: int = 1, compression=None):
    """Returns train_step(params, opt_state, batch) ->
    (params, opt_state, metrics).  ``compression`` is an optional
    repro.distributed.compression.Compressor applied to the accumulated
    gradient before the optimizer."""

    def grad_fn(params, mb):
        return jax.value_and_grad(
            lambda p: loss_fn(p, cfg, mb), has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            k = microbatches
            mbs = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)
            # bf16-master models accumulate in bf16: their cotangents are
            # already bf16, and an f32 accumulator makes XLA materialize
            # f32 copies of every param-grad buffer (~3x grad memory);
            # f32-master models keep exact f32 accumulation.
            acc_dt = (jnp.bfloat16 if cfg.param_dtype == "bfloat16"
                      else jnp.float32)
            gacc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)

            def body(carry, mb):
                gacc, macc = carry
                (loss, metrics), g = grad_fn(params, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gacc, g)
                macc = jax.tree.map(lambda a, b: a + b, macc, metrics)
                return (gacc, macc), None

            m0 = jax.eval_shape(lambda p, b: grad_fn(p, b)[0][1], params,
                                jax.tree.map(lambda x: x[0], mbs))
            m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
            (grads, msum), _ = lax.scan(body, (gacc0, m0), mbs)
            grads = jax.tree.map(lambda g: g / k, grads)
            metrics = jax.tree.map(lambda m: m / k, msum)

        if compression is not None:
            grads = compression.roundtrip(grads)
        with jax.named_scope("optimizer"):
            params, opt_state, stats = apply_updates(ocfg, params, grads,
                                                     opt_state)
        metrics = dict(metrics)
        metrics.update(stats)
        return params, opt_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = loss_fn(params, cfg, batch)
        return metrics
    return eval_step


# ---------------------------------------------------------------------------
# microbatch auto-resolution (used by the dry-run and the trainer)

HBM_BYTES = 16 * 2**30          # TPU v5e: the dry-run's explicit target
ACT_BUDGET = 0.45               # fraction of HBM available for activations


def _device_act_budget() -> Optional[int]:
    """Activation budget of this process's first device, from the HBM
    limit it reports; None where it reports none (the CPU backend)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit * ACT_BUDGET) if limit else None


def resolve_microbatches(cfg: ModelConfig, global_batch: int, seq: int,
                         data_shards: int, budget_bytes: int = None) -> int:
    """Smallest power-of-two k such that the per-device layer-carry stacks
    (the dominant remat residual: ~6 bytes/elem — bf16 saved carry plus the
    f32 copy XLA materializes on this backend) fit the activation budget:
    ``budget_bytes``, else the device's (``_device_act_budget``).  With no
    budget at all there is nothing to fit and k is 1."""
    budget = budget_bytes or _device_act_budget()
    if budget is None:
        return 1
    per_dev_batch = max(global_batch // data_shards, 1)
    d_eff = cfg.d_model
    if cfg.family in ("ssm", "hybrid"):
        d_eff = max(d_eff, cfg.ssm.expand * cfg.d_model)
    eff_layers = cfg.n_layers
    if cfg.remat_group > 1 and cfg.n_layers % cfg.remat_group == 0:
        # nested remat keeps G group carries + L/G transient carries
        eff_layers = cfg.remat_group + cfg.n_layers // cfg.remat_group
    if cfg.family == "vlm" and cfg.cross_attn_every:
        eff_layers = (cfg.n_layers // cfg.cross_attn_every
                      + cfg.cross_attn_every)
    stack_bytes = eff_layers * per_dev_batch * seq * d_eff * 6
    if cfg.moe.n_experts:
        # MoE dispatch/combine (f32, ~Tg*topk*cf elems per token) and the
        # (E, C, d) expert buffers are per-layer transients that scale with
        # per-microbatch tokens; x2 for fwd+bwd-recompute concurrency.
        m = cfg.moe
        kcf = m.top_k * m.capacity_factor
        per_tok = m.group_tokens * kcf * 8 + 4 * kcf * cfg.d_model * 2
        stack_bytes += int(per_dev_batch * seq * per_tok * 4)
    k = 1
    while stack_bytes // k > budget:
        nk = k * 2
        if global_batch % nk or (global_batch // nk) % data_shards:
            break
        k = nk
    return k
