"""Jit'd public wrapper around the flash-attention Pallas kernel.

The kernels run compiled on a TPU and in the Pallas interpreter on any
other backend (``repro.kernels.platform``).  The wrapper adapts the
model-side (B, S, H, D) layout to the kernels' (B, H, S, D) TPU-friendly
layout.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap: float = 0.0):
    """Model-layout wrapper: q (B, Sq, H, D); k/v (B, Sk, KVH, D)."""
    win = int(window) if window else 0
    out = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=win,
        softcap=softcap)
    return out.transpose(0, 2, 1, 3)
