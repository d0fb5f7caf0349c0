"""Pallas TPU kernel for the Mamba2 SSD chunk computation.

One grid cell = one (batch, chunk, head): VMEM working set is the chunk's
x (L, P), B/C (L, N), decay vector (L,) — tens of KB, far under VMEM —
and the compute is two MXU matmuls: the (L, L) masked intra-chunk kernel
and the (N, P) chunk-state outer product.  The cross-chunk recurrence is
a cheap jnp scan outside the kernel (O(nc) sequential steps over (N, P)
states), mirroring the ssd_chunked decomposition in repro.models.ssm.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode


def _chunk_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref,
                  y_ref, state_ref, *, L: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)         # (L, P)
    B = b_ref[0, 0].astype(jnp.float32)            # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)            # (L, N)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)       # (1, L) row
    cum = cum_ref[0, 0, 0].astype(jnp.float32)     # (1, L) row

    # intra-chunk: M[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j <= i
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    cum_j = jnp.broadcast_to(cum, (L, L))          # [i, j] = cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # masked in the exponent: above the diagonal exp(cum_i - cum_j) overflows
    decay = jnp.exp(jnp.where(ii >= jj, cum_j.T - cum_j, -jnp.inf))
    M = CB * decay * dt
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # chunk state: S = sum_j exp(cum_L - cum_j) dt_j B_j (x) x_j -> (N, P)
    w = jnp.exp(cum[:, L - 1:] - cum) * dt                        # (1, L)
    state = jax.lax.dot_general(B.T * w, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)


def ssd_chunk_pallas(x, B, C, dt, cum, *,
                     interpret: Optional[bool] = None):
    """Intra-chunk SSD via Pallas.

    x:   (b, nc, L, nh, P)  f32/bf16
    B,C: (b, nc, L, N)
    dt:  (b, nc, L, nh)
    cum: (b, nc, L, nh)     cumulative sum of dt*A within each chunk
    Returns (y_intra (b, nc, L, nh, P) f32, states (b, nc, nh, N, P) f32).
    ``interpret=None`` follows the backend (``interpret_mode``).
    """
    if interpret is None:
        interpret = interpret_mode()
    b, nc, L, nh, P = x.shape
    N = B.shape[-1]
    # layout: put the head axis on the grid; dt/cum become (1, L) rows so
    # every block's last two dims are whole array dims or lane-aligned
    xg = x.transpose(0, 1, 3, 2, 4)          # (b, nc, nh, L, P)
    dtg = dt.transpose(0, 1, 3, 2)[:, :, :, None, :]    # (b, nc, nh, 1, L)
    cumg = cum.transpose(0, 1, 3, 2)[:, :, :, None, :]

    kernel = functools.partial(_chunk_kernel, L=L)
    y, states = pl.pallas_call(
        kernel,
        grid=(b, nc, nh),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, nh, L, P), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, nh, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(xg, B, C, dtg, cumg)
    return y.transpose(0, 1, 3, 2, 4), states


def ssd_scan(x, B, C, dt, A, D, chunk: int, *,
             interpret: Optional[bool] = None):
    """Full SSD: Pallas intra-chunk + jnp inter-chunk recurrence.

    Shapes as in repro.kernels.ref.ssd_ref; returns (y, h_final)."""
    b, S, nh, P = x.shape
    N = B.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, f"S={S} % chunk={L}"
    nc = S // L

    xc = x.reshape(b, nc, L, nh, P)
    Bc = B.reshape(b, nc, L, N)
    Cc = C.reshape(b, nc, L, N)
    dtc = dt.reshape(b, nc, L, nh).astype(jnp.float32)
    cum = jnp.cumsum(dtc * A[None, None, None, :], axis=2)

    y_intra, states = ssd_chunk_pallas(xc, Bc, Cc, dtc, cum,
                                       interpret=interpret)

    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (b, nc, nh)

    def body(h, inp):
        s_c, cd = inp
        h_prev = h
        return h * cd[..., None, None] + s_c, h_prev

    h0 = jnp.zeros((b, nh, N, P), jnp.float32)
    h_final, h_prevs = lax.scan(
        body, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)              # (b, nc, nh, N, P)

    y_inter = jnp.einsum("bcln,bchnp->bclhp", Cc, h_prevs) \
        * jnp.exp(cum)[..., None]
    y = y_intra + y_inter + D[None, None, None, :, None] \
        * xc.astype(jnp.float32)
    return y.reshape(b, S, nh, P), h_final
