"""XLA-native flash attention with a memory-efficient custom VJP.

The forward pass is the blocked online-softmax scan (no (Sq, Sk) score
matrix).  Without a custom VJP, JAX's autodiff of that scan stacks the
per-chunk probabilities for the backward pass — O(Sq x Sk) memory, exactly
what flash attention exists to avoid (measured: 28 GiB/device for
qwen2-7b train_4k).  The custom backward recomputes probabilities per
chunk from (q, k, v, lse) like FlashAttention-2, so residuals are just
(q, k, v, out, lse).

This module is the lowering used by the multi-pod dry-run; the Pallas TPU
kernel in ``repro.kernels`` implements the same algorithm with explicit
VMEM tiling and is validated against the same oracle.

``window`` is passed as an f32 array (jnp.inf = no window) so that gemma3
can select local/global windows per-layer inside the layer scan while
keeping this function's static argnums hashable.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _bias(q_pos, k_pos, causal: bool, window, k_limit) -> jnp.ndarray:
    dq = q_pos[:, None].astype(jnp.float32)
    dk = k_pos[None, :].astype(jnp.float32)
    ok = dk < k_limit
    if causal:
        ok = ok & (dq >= dk)
    ok = ok & (dq - dk < window)
    return jnp.where(ok, 0.0, NEG_INF)


def _pad_kv(k, v, block_k):
    Sk = k.shape[1]
    if Sk % block_k:
        pad = block_k - Sk % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return k, v


def _chunks(a, block):
    B, S, H, D = a.shape
    return a.reshape(B, S // block, block, H, D).transpose(1, 0, 2, 3, 4)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_xla(q, k, v, window, causal: bool = True,
                        block_k: int = 512, softcap: float = 0.0,
                        q_offset: int = 0, scale: Optional[float] = None):
    """q: (B,Sq,H,D); k,v: (B,Sk,KVH,D); window: f32 scalar (inf = none);
    ``scale`` multiplies the f32 scores, 1/sqrt(D) where None.

    Returns (B,Sq,H,D) in q.dtype."""
    out, _ = _flash_fwd_impl(q, k, v, window, causal, block_k, softcap,
                             q_offset, scale)
    return out


def _flash_fwd_impl(q, k, v, window, causal, block_k, softcap, q_offset,
                    scale):
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = D ** -0.5 if scale is None else scale
    block_k = min(block_k, Sk)
    k_limit = Sk
    k, v = _pad_kv(k, v, block_k)

    qg = q.reshape(B, Sq, KVH, G, D)
    m0 = jnp.full((B, Sq, KVH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KVH, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc, idx = carry
        kb, vb = inputs
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = idx * block_k + jnp.arange(block_k)
        s = s + _bias(q_pos, k_pos, causal, window, k_limit)[None, :, None,
                                                             None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc, idx + 1), None

    (m, l, acc, _), _ = lax.scan(
        body, (m0, l0, acc0, jnp.int32(0)), (_chunks(k, block_k),
                                             _chunks(v, block_k)))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(B, Sq, H, D)
    # rows with no valid keys: lse=+inf makes backward probabilities zero
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
    return out.astype(q.dtype), lse


def _flash_fwd(q, k, v, window, causal, block_k, softcap, q_offset, scale):
    out, lse = _flash_fwd_impl(q, k, v, window, causal, block_k, softcap,
                               q_offset, scale)
    return out, (q, k, v, window, out, lse)


def _flash_bwd(causal, block_k, softcap, q_offset, scale, res, do):
    q, k, v, window, out, lse = res
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = D ** -0.5 if scale is None else scale
    block_k = min(block_k, Sk)
    k_limit = Sk
    kp, vp = _pad_kv(k, v, block_k)

    qg = q.reshape(B, Sq, KVH, G, D)
    dog = do.reshape(B, Sq, KVH, G, D)
    outg = out.reshape(B, Sq, KVH, G, D)
    # D_i = sum_d do_i * o_i (f32)
    Dvec = jnp.einsum("bqhgd,bqhgd->bqhg", dog.astype(jnp.float32),
                      outg.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)

    def body(dq_acc, inputs):
        kb, vb, idx = inputs
        s_raw = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            t = jnp.tanh(s_raw / softcap)
            s = t * softcap
        else:
            s = s_raw
        k_pos = idx * block_k + jnp.arange(block_k)
        s = s + _bias(q_pos, k_pos, causal, window, k_limit)[None, :, None,
                                                             None, :]
        p = jnp.exp(s - lse[..., None])                        # exact probs
        dv_b = jnp.einsum("bqhgk,bqhgd->bkhd", p.astype(do.dtype), dog,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dog, vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Dvec[..., None])
        if softcap > 0.0:
            ds = ds * (1.0 - jnp.square(t))
        ds = ds * scale
        dq_acc = dq_acc + jnp.einsum("bqhgk,bkhd->bqhgd",
                                     ds.astype(kb.dtype), kb,
                                     preferred_element_type=jnp.float32)
        dk_b = jnp.einsum("bqhgk,bqhgd->bkhd", ds.astype(q.dtype), qg,
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_b.astype(k.dtype), dv_b.astype(v.dtype))

    idxs = jnp.arange(kp.shape[1] // block_k, dtype=jnp.int32)
    dq0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)
    dq, (dk_chunks, dv_chunks) = lax.scan(
        body, dq0, (_chunks(kp, block_k), _chunks(vp, block_k), idxs))

    def unchunk(c):
        a = c.transpose(1, 0, 2, 3, 4).reshape(B, -1, KVH, D)
        return a[:, :Sk]

    dq = dq.reshape(B, Sq, H, D).astype(q.dtype)
    return (dq, unchunk(dk_chunks), unchunk(dv_chunks),
            jnp.zeros_like(window))


flash_attention_xla.defvjp(_flash_fwd, _flash_bwd)
