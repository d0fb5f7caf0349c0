"""Common model primitives: norms, RoPE, attention (blocked online-softmax),
MLPs, embeddings.

Conventions
-----------
* Parameters are nested dicts of ``jnp.ndarray``; layer stacks carry a
  leading ``n_layers`` axis and are consumed with ``lax.scan``.
* Compute runs in ``cfg.compute_dtype`` (bf16 by default); softmax, norm
  statistics and loss run in f32.
* Attention is GQA-general: ``n_heads`` query heads grouped over
  ``n_kv_heads`` KV heads.  The blocked implementation scans over KV chunks
  with an online softmax so that no (S_q, S_k) score matrix is ever
  materialized — this is the XLA-lowered analogue of the Pallas flash
  kernel in ``repro.kernels`` and is what the multi-pod dry-run compiles.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# dtype helpers

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def dtype_of(name: str):
    return _DTYPES[name]


# ---------------------------------------------------------------------------
# initializers


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    """Scaled-normal init; fan_in defaults to the second-to-last dim."""
    if fan_in is None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return _normal(key, shape, fan_in ** -0.5, dtype)


def embed_init(key, shape, dtype):
    return _normal(key, shape, 1.0, dtype)


# ---------------------------------------------------------------------------
# norms


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with f32 statistics but NO full-size f32 tensors.

    Converting the whole input to f32 (the textbook formulation) makes the
    first op of a scanned layer a convert-of-the-carry; XLA hoists that
    convert out of the backward loop and materializes an f32 copy of the
    entire saved-carry stack (~2x remat memory).  Keeping the full-size
    math in the input dtype with an f32 (..., 1) scale avoids it; only the
    reduction accumulates in f32."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    scale = lax.rsqrt(var + eps).astype(x.dtype)
    return (x * scale) * (1.0 + weight).astype(x.dtype)


def rms_norm_init(d: int) -> jnp.ndarray:
    # stored as (weight - 1) so zeros == identity (gemma convention)
    return jnp.zeros((d,), jnp.float32)


# ---------------------------------------------------------------------------
# rotary position embeddings


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float):
    """cos/sin tables for given integer positions; shapes (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (..., S, H, D); cos/sin: (S, D/2) or broadcastable (..., S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # broadcast (S, D/2) across batch and heads: (..., S, H, D/2)
    c = cos[..., :, None, :].astype(x.dtype)
    s = sin[..., :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal absolute position embeddings (n, d)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10_000.0) / max(half - 1, 1)))
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


# ---------------------------------------------------------------------------
# attention parameter init


def attention_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dt, fan_in=d),
        "wk": dense_init(ks[1], (d, kvh, hd), dt, fan_in=d),
        "wv": dense_init(ks[2], (d, kvh, hd), dt, fan_in=d),
        "wo": dense_init(ks[3], (h, hd, d), dt, fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dt)
        p["bk"] = jnp.zeros((kvh, hd), dt)
        p["bv"] = jnp.zeros((kvh, hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd)
        p["k_norm"] = rms_norm_init(hd)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)  # tanh-gated cross attention
    return p


def project_qkv(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                kv_x: Optional[jnp.ndarray] = None):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S_kv,KVH,hd)."""
    cdt = dtype_of(cfg.compute_dtype)
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cdt), p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", kv_x.astype(cdt), p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", kv_x.astype(cdt), p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def project_out(p: dict, cfg: ModelConfig, o: jnp.ndarray) -> jnp.ndarray:
    cdt = dtype_of(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", o.astype(cdt), p["wo"].astype(cdt))


# ---------------------------------------------------------------------------
# blocked (online-softmax) multi-head attention

NEG_INF = -1e30


def _chunk_bias(q_pos, k_pos, causal: bool, window) -> jnp.ndarray:
    """Additive mask bias (..., S_q, block_k) from position vectors."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(dq.shape, dk.shape), jnp.bool_)
    if causal:
        ok = ok & (dq >= dk)
    if window is not None:
        ok = ok & (dq - dk < window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      *, causal: bool, window=None,
                      block_k: int = 512, softcap: float = 0.0,
                      q_offset: int = 0) -> jnp.ndarray:
    """GQA attention scanning over KV chunks with an online softmax.

    q: (B, Sq, H, D); k, v: (B, Sk, KVH, D); returns (B, Sq, H, D).
    ``window`` may be None, a python int, or a traced scalar (gemma3 selects
    the window inside the layer scan).  ``q_offset`` shifts query positions
    (used by decode paths that fall back to this implementation).
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = D ** -0.5

    block_k = min(block_k, Sk)
    if Sk % block_k:
        # pad the KV sequence up to a chunk multiple; padded keys are
        # masked out below via the k_pos < Sk check
        pad = block_k - Sk % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // block_k
    k_limit = Sk

    qg = q.reshape(B, Sq, KVH, G, D) * scale
    # scan carries: running max m, normalizer l, accumulator acc (f32)
    m0 = jnp.full((B, Sq, KVH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KVH, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)

    kc = k.reshape(B, n_chunks, block_k, KVH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, block_k, KVH, D).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc, idx = carry
        kb, vb = inputs  # (B, block_k, KVH, D)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                       preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = idx * block_k + jnp.arange(block_k)
        bias = _chunk_bias(q_pos, k_pos, causal, window)  # (Sq, block_k)
        bias = bias + jnp.where(k_pos < k_limit, 0.0, NEG_INF)[None, :]
        s = s + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new, idx + 1), None

    (m, l, acc, _), _ = lax.scan(body, (m0, l0, acc0, jnp.int32(0)), (kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def naive_attention(q, k, v, *, causal: bool, window=None, softcap: float = 0.0,
                    q_offset: int = 0, k_valid=None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Reference full-score attention.  Also the decode path (Sq == 1),
    where the score matrix is (B, H, 1, Sk) and therefore small.

    ``k_valid``: optional (B,) number of valid cache positions per sequence.
    ``scale``: the scores' factor, 1/sqrt(D) by default.
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, D) * (D ** -0.5 if scale is None else scale)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k,
                   preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    # q_pos: (Bq, Sq) where Bq is 1 (shared offset) or B (per-seq decode pos)
    q_pos = jnp.atleast_1d(jnp.asarray(q_offset))[:, None] + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    bias = _chunk_bias(q_pos, k_pos[None], causal, window)   # (Bq, Sq, Sk)
    s = s + bias[:, :, None, None, :]
    if k_valid is not None:
        valid = k_pos[None, :] < k_valid[:, None]            # (B, Sk)
        s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def attention(cfg: ModelConfig, q, k, v, *, causal, window=None,
              softcap: float = 0.0, q_offset: int = 0, k_valid=None,
              scale: Optional[float] = None):
    """Dispatch on cfg.attention_impl and query length.  ``scale`` is the
    scores' factor, 1/sqrt(head_dim) where None."""
    if q.shape[1] == 1 or cfg.attention_impl == "naive" or k_valid is not None:
        return naive_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset,
                               k_valid=k_valid, scale=scale)
    if cfg.attention_impl == "pallas":
        if scale is not None:
            raise ValueError("the Pallas attention kernel scales by "
                             "1/sqrt(head_dim) only")
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap)
    from repro.models.flash import flash_attention_xla
    win = (jnp.float32(jnp.inf) if window is None
           else jnp.asarray(window, jnp.float32))
    return flash_attention_xla(
        q, k, v, win, causal, min(cfg.attention_block_k, k.shape[1]),
        softcap, q_offset, scale)


# ---------------------------------------------------------------------------
# MLP (gated: SwiGLU / GeGLU)


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w1": dense_init(k1, (d, ff), dt, fan_in=d),      # gate/up proj
        "w2": dense_init(k3, (ff, d), dt, fan_in=ff),     # down proj
    }
    if cfg.mlp_gated:
        p["w3"] = dense_init(k2, (d, ff), dt, fan_in=d)   # up proj
    return p


def activation(name: str):
    """silu; gelu_erf, the exact GELU; else the tanh form of GELU."""
    if name == "silu":
        return jax.nn.silu
    return partial(jax.nn.gelu, approximate=name != "gelu_erf")


def mlp_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    cdt = dtype_of(cfg.compute_dtype)
    act = activation(cfg.act)
    x = x.astype(cdt)
    h = act(x @ p["w1"].astype(cdt))
    if cfg.mlp_gated:
        h = h * (x @ p["w3"].astype(cdt))
    return h @ p["w2"].astype(cdt)


# ---------------------------------------------------------------------------
# embeddings / unembedding


def embedding_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p = {"embed": embed_init(k1, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dt,
                                  fan_in=cfg.d_model)
    return p


def embed_tokens(p: dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    cdt = dtype_of(cfg.compute_dtype)
    x = jnp.take(p["embed"], tokens, axis=0).astype(cdt)
    if cfg.family == "dense" and cfg.qk_norm:   # gemma scales embeddings
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    return x


def unembed_matrix(p: dict, cfg: ModelConfig) -> jnp.ndarray:
    cdt = dtype_of(cfg.compute_dtype)
    if cfg.tie_embeddings:
        return p["embed"].astype(cdt).T
    return p["unembed"].astype(cdt)
