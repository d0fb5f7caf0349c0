"""Pallas TPU flash-attention kernel.

TPU-native tiling (DESIGN.md §2 hardware adaptation): the grid is
(batch, q_heads, q_blocks, k_blocks) with the innermost k dimension
iterated sequentially per TPU core, so the online-softmax state
(m, l, acc) lives in VMEM scratch across k iterations.  Block shapes are
MXU-aligned (128 x head_dim); GQA is handled by the K/V BlockSpec index
map (h -> h // group) so KV heads are never replicated in HBM.

Validated in interpret mode against ``repro.kernels.ref.attention_ref``
(and transitively against repro.models.flash's custom-VJP XLA fallback,
which lowers the same algorithm for the CPU dry-run).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            block_q: int, block_k: int, k_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (block_q, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (block_k, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    ok = k_pos < k_len
    if causal:
        ok = ok & (q_pos >= k_pos)
    if window > 0:
        ok = ok & (q_pos - k_pos < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int = 0, softcap: float = 0.0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None):
    """q: (B, H, Sq, D); k, v: (B, KVH, Sk, D).  Returns (B, H, Sq, D).

    ``window`` <= 0 means full attention.  Sq/Sk are padded to block
    multiples internally; padded keys are masked via ``k_len``.
    ``interpret=None`` follows the backend (``interpret_mode``)."""
    if interpret is None:
        interpret = interpret_mode()
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    G = H // KVH
    scale = D ** -0.5

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, k_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
