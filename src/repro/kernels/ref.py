"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0, scale: Optional[float] = None):
    """q: (B, Sq, H, D); k, v: (B, Sk, KVH, D) -> (B, Sq, H, D), in f32
    throughout; ``scale`` 1/sqrt(D) where None."""
    B, Sq, H, D = q.shape
    KVH, Sk = k.shape[2], k.shape[1]
    G = H // KVH
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5 if scale is None
                                             else scale)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok = ok & (q_pos >= k_pos)
    if window > 0:
        ok = ok & (q_pos - k_pos < window)
    s = jnp.where(ok[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
