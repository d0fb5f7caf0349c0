"""Plain float32 building blocks shared by the reference models.

Nothing here imports the system under test.  Every matrix product goes
through ``Dot``, which computes in float32 at ``highest`` precision, or,
for the control that decides whether the comparison is tight enough, a
step below the bfloat16 that the configurations compute in: float8
(e4m3, one scale per tensor) wherever the program holds bfloat16, that
is the operands of every product of the forward and the backward, and
the residual stream between blocks (``Dot.act``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


@jax.custom_vjp
def _f8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _f8_fwd(x):
    return _f8_round(x), None


def _f8_bwd(_, ct):
    return (ct,)            # straight through: the forward operand is rounded


_f8_round.defvjp(_f8_fwd, _f8_bwd)


@jax.custom_vjp
def _f8_round_ct(y):
    """Identity forward; rounds the cotangent, the operand that the
    backward's products take in the output's place."""
    return y


def _f8_ct_fwd(y):
    return y, None


def _f8_ct_bwd(_, ct):
    return (_f8_round(ct),)


_f8_round_ct.defvjp(_f8_ct_fwd, _f8_ct_bwd)


class Dot:
    """``einsum`` at the reference's precision ("f32" or "fp8")."""

    def __init__(self, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.precision = precision

    def __call__(self, spec: str, *operands):
        if self.precision == "f32":
            return jnp.einsum(spec, *operands, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
        out = jnp.einsum(spec, *[_f8_round(o) for o in operands],
                         precision=HIGHEST, preferred_element_type=jnp.float32)
        return _f8_round_ct(out)

    def act(self, x):
        """The residual stream as the configuration's compute dtype holds
        it: exact at f32, rounded at the control's fp8."""
        return x if self.precision == "f32" else _f8_round(x)


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def rms_norm(x, w, eps):
    """Weights are stored as (gain - 1), so zeros are the identity."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + w)


def cross_entropy_loss(hidden, embed, tokens, dot: Dot):
    """Mean next-token cross entropy of ``hidden`` (B, S, d) against the
    tied embedding (V, d): position s predicts token s + 1.  Computed one
    row at a time so the (S, V) logits of only one row are alive."""

    @jax.checkpoint
    def row(args):
        h, t = args
        logits = dot("sd,vd->sv", h[:-1], embed)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[1:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total = jnp.sum(lax.map(row, (hidden, tokens)))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def adamw_step(opt: dict, params, grads, m, v, step):
    """One AdamW update with global-norm clipping and linear warm-up;
    ``step`` counts from 0 and may be traced.  Returns (params, m, v,
    grad_norm, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    lr = opt["lr"] * jnp.minimum(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    t = jnp.asarray(step + 1, jnp.float32)
    b1, b2 = opt["b1"], opt["b2"]
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * jnp.square(x), v, g)

    def upd(p, mi, vi):
        mhat = mi / (1 - b1 ** t)
        vhat = vi / (1 - b2 ** t)
        delta = mhat / (jnp.sqrt(vhat) + opt["eps"]) + opt["weight_decay"] * p
        return p - lr * delta

    params = jax.tree.map(upd, params, m, v)
    return params, m, v, gnorm, g


def head_chunk(batch: int, seq: int, heads: int, budget: int = 1 << 27) -> int:
    """Heads per block such that one (B, S, S, heads) f32 block stays
    under ``budget`` bytes; a divisor of ``heads``."""
    per_head = batch * seq * seq * 4
    hc = max(1, min(heads, budget // max(per_head, 1)))
    while heads % hc:
        hc -= 1
    return hc


def inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def fan_in_std(n: int) -> float:
    return 1.0 / math.sqrt(n)
