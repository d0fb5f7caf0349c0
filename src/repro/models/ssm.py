"""Mamba2 blocks via SSD (state-space duality, arXiv:2405.21060).

Discrete recurrence per head (state N, head dim P):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * (B_t ⊗ x_t)      h: (N, P)
    y_t = C_t · h_t + D * x_t

Training and prefill use the chunked SSD algorithm: quadratic
attention-like compute inside length-L chunks plus a linear inter-chunk
state recurrence, through ``ssd``: the fused Pallas kernels on a TPU,
else the XLA ``ssd_chunked``.  A step-by-step ``reference_scan`` (the
tests' oracle) and a single-token ``mamba_decode_step`` are provided.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import activation_axes
from repro.kernels import platform, ssd_scan
from repro.models.layers import dense_init, dtype_of, rms_norm, rms_norm_init


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.state_dim


def mamba_init(key, cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nh, P, N = dims(cfg)
    conv_ch = d_inner + 2 * N
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    # packed input projection: [z (d_inner), xBC (d_inner + 2N), dt (nh)]
    d_proj = 2 * d_inner + 2 * N + nh
    dt_init = np.exp(
        np.random.RandomState(0).uniform(
            np.log(s.dt_min), np.log(s.dt_max), size=(nh,)).astype("float32"))
    return {
        "in_proj": dense_init(ks[0], (d, d_proj), dt, fan_in=d),
        "conv_w": dense_init(ks[1], (s.conv_width, conv_ch), dt,
                             fan_in=s.conv_width),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((nh,), jnp.float32),        # A = -exp(A_log) = -1
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.asarray(np.log(np.expm1(dt_init)), jnp.float32),
        "norm": rms_norm_init(d_inner),
        "out_proj": dense_init(ks[3], (d_inner, d), dt, fan_in=d_inner),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    d_inner, nh, P, N = dims(cfg)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt_raw = zxbcdt[..., 2 * d_inner + 2 * N:]
    return z, xBC, dt_raw


@jax.named_scope("conv")
def causal_conv(xBC: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv over time.  xBC: (B, S, Ch), w: (W, Ch).

    If ``state`` (B, W-1, Ch) is given it is prepended (decode streaming);
    returns (out, new_state).
    """
    W = w.shape[0]
    if state is not None:
        xpad = jnp.concatenate([state.astype(xBC.dtype), xBC], axis=1)
    else:
        xpad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xpad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(W))
    out = out + b[None, None, :]
    new_state = xpad[:, -(W - 1):, :] if W > 1 else None
    return jax.nn.silu(out), new_state


def _preprocess(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                conv_state: Optional[jnp.ndarray] = None):
    """Shared front half: in_proj + conv + dt/A."""
    cdt = dtype_of(cfg.compute_dtype)
    with jax.named_scope("in_proj"):
        zxbcdt = x.astype(cdt) @ p["in_proj"].astype(cdt)
    z, xBC, dt_raw = _split_proj(cfg, zxbcdt)
    xBC, new_conv = causal_conv(xBC, p["conv_w"].astype(cdt),
                                p["conv_b"].astype(cdt), conv_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["A_log"])                                          # (nh,)
    return z, xBC, dt, A, new_conv


def split_xbc(xBC: jnp.ndarray, n_heads: int, state: int):
    """The conv output [x | B | C] (B, S, nh*P + 2N) -> x as heads
    (B, S, nh, P), and B, C (B, S, N) in f32."""
    d_inner = xBC.shape[-1] - 2 * state
    xh = xBC[..., :d_inner].reshape(
        xBC.shape[:-1] + (n_heads, d_inner // n_heads))
    return (xh, xBC[..., d_inner:d_inner + state].astype(jnp.float32),
            xBC[..., d_inner + state:].astype(jnp.float32))


@jax.named_scope("out_proj")
def _finish(p: dict, cfg: ModelConfig, y_heads: jnp.ndarray, z: jnp.ndarray):
    cdt = dtype_of(cfg.compute_dtype)
    B_, S_ = z.shape[0], z.shape[1]
    d_inner = z.shape[-1]
    y = y_heads.reshape(B_, S_, d_inner).astype(cdt)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    return y @ p["out_proj"].astype(cdt)


@jax.named_scope("ssd")
def ssd_chunked(xh, Bmat, Cmat, dt, A, D, chunk: int,
                h_init: Optional[jnp.ndarray] = None,
                intra_dtype=jnp.float32):
    """Chunked SSD scan.

    xh: (B, S, nh, P); Bmat/Cmat: (B, S, N); dt: (B, S, nh); A: (nh,).
    Returns (y (B,S,nh,P) in intra_dtype (f32-accumulated), h_final
    (B, nh, N, P) f32).  ``intra_dtype=bf16`` keeps the full-size
    intra-chunk tensors in bf16 (HBM traffic ~halves); the inter-chunk
    states and decay math stay f32."""
    B_, S, nh, P = xh.shape
    N = Bmat.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, f"seq {S} not divisible by chunk {L}"
    nc = S // L

    xf = xh.astype(intra_dtype).reshape(B_, nc, L, nh, P)
    Bc = Bmat.reshape(B_, nc, L, N)
    Cc = Cmat.reshape(B_, nc, L, N)
    dtc = dt.reshape(B_, nc, L, nh)

    dA = dtc * A[None, None, None, :]                   # (B,nc,L,nh) <= 0
    cum = jnp.cumsum(dA, axis=2)                        # (B,nc,L,nh)

    # ---- intra-chunk (quadratic within chunk) ------------------------------
    CB = jnp.einsum("bcln,bcmn->bclm", Cc, Bc)          # (B,nc,L,L)
    # decay[b,c,i,j,h] = exp(cum_i - cum_j) for j <= i, else 0.  Mask the
    # exponent, not the product: above the diagonal cum_i - cum_j > 0 grows
    # with the chunk, exp overflows to inf, and inf * 0 is NaN.
    tri = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None]
    seg = cum[..., :, None, :] - cum[..., None, :, :]             # (B,nc,L,L,nh)
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    M = CB[..., None] * decay                                     # (B,nc,L,L,nh)
    M = (M * dtc[:, :, None, :, :]).astype(intra_dtype)  # weight by dt_j
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xf,
                         preferred_element_type=intra_dtype)

    # ---- chunk states -------------------------------------------------------
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dtc          # (B,nc,L,nh)
    S_c = jnp.einsum("bcln,bclh,bclhp->bchnp", Bc, w,
                     xf.astype(jnp.float32))             # (B,nc,nh,N,P)

    # ---- inter-chunk recurrence --------------------------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])             # (B,nc,nh)
    h0 = (jnp.zeros((B_, nh, N, P), jnp.float32)
          if h_init is None else h_init.astype(jnp.float32))

    def body(h, inp):
        s_c, cd = inp                                   # (B,nh,N,P), (B,nh)
        h_prev = h
        h = h * cd[..., None, None] + s_c
        return h, h_prev

    (h_final, h_prevs) = lax.scan(
        body, h0, (S_c.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)          # (B,nc,nh,N,P)

    y_inter = (jnp.einsum("bcln,bchnp->bclhp", Cc, h_prevs)
               * jnp.exp(cum)[..., None]).astype(intra_dtype)
    y = y_intra + y_inter \
        + (D[None, None, None, :, None] * xf.astype(jnp.float32)
           ).astype(intra_dtype)
    return y.reshape(B_, S, nh, P), h_final


def reference_scan(xh, Bmat, Cmat, dt, A, D,
                   h_init: Optional[jnp.ndarray] = None):
    """Step-by-step oracle recurrence (tests / kernel ref)."""
    B_, S, nh, P = xh.shape
    N = Bmat.shape[-1]
    h0 = (jnp.zeros((B_, nh, N, P), jnp.float32)
          if h_init is None else h_init.astype(jnp.float32))

    def body(h, inp):
        x_t, b_t, c_t, dt_t = inp   # (B,nh,P), (B,N), (B,N), (B,nh)
        a_t = jnp.exp(dt_t * A[None, :])                       # (B,nh)
        upd = jnp.einsum("bn,bhp,bh->bhnp", b_t, x_t.astype(jnp.float32), dt_t)
        h = h * a_t[..., None, None] + upd
        y_t = jnp.einsum("bn,bhnp->bhp", c_t, h) + \
            D[None, :, None] * x_t.astype(jnp.float32)
        return h, y_t

    xs = (xh.transpose(1, 0, 2, 3), Bmat.transpose(1, 0, 2),
          Cmat.transpose(1, 0, 2), dt.transpose(1, 0, 2))
    h_final, ys = lax.scan(body, h0, xs)
    return ys.transpose(1, 0, 2, 3), h_final


def _intra_dtype(cfg: ModelConfig):
    return dtype_of(cfg.ssm.intra_dtype)


# ``ssd_paths()`` collects one bool per ``ssd`` call traced while it is
# open: True where the call took the fused kernel.
_SSD_PATHS = platform.PathLog()
ssd_paths = _SSD_PATHS.collect


def ssd(xBC, dt, A, D, chunk: int, *, state: int, intra_dtype=jnp.float32,
        out_dtype=None):
    """The SSD scan of every Mamba2 block, from the conv output [x | B | C]
    (B, S, nh*P + 2N), dt (B, S, nh) and A, D (nh,).  Takes the fused
    Pallas kernel (``repro.kernels.ssd_scan``) on a TPU, in a program with
    no sharding policy (the kernel is not partitioned), at shapes it
    tiles; else ``ssd_chunked``.  Returns (y (B, S, nh, P) in
    ``out_dtype``, default ``intra_dtype``; h_final (B, nh, N, P) f32)."""
    _, S, nh = dt.shape
    P = (xBC.shape[-1] - 2 * state) // nh
    use_kernel = (platform.on_tpu() and activation_axes() is None
                  and ssd_scan.tiles(S, nh, P, state, chunk))
    _SSD_PATHS.record(use_kernel)
    if use_kernel:
        # XLA lays the conv's output out channels-major, as the kernel
        # reads it, so this transpose is free; it lands in the conv's last
        # fusion, whose work it is scoped with
        with jax.named_scope("conv"):
            xbc_t = jnp.swapaxes(xBC, 1, 2)
        with jax.named_scope("ssd"):
            return ssd_scan.ssd_pallas(xbc_t, dt, A, D, chunk, state=state,
                                       intra_dtype=intra_dtype,
                                       out_dtype=out_dtype)
    y, h_final = ssd_chunked(*split_xbc(xBC, nh, state), dt, A, D, chunk,
                             intra_dtype=intra_dtype)
    return y.astype(out_dtype or y.dtype), h_final


def mamba_forward(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Full-sequence mamba block. x: (B, S, d) -> (B, S, d)."""
    z, xBC, dt, A, _ = _preprocess(p, cfg, x)
    y, _ = ssd(xBC, dt, A, p["D"], cfg.ssm.chunk_size,
               state=cfg.ssm.state_dim, intra_dtype=_intra_dtype(cfg),
               out_dtype=dtype_of(cfg.compute_dtype))
    return _finish(p, cfg, y, z)


def mamba_prefill(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Forward that also returns (conv_state, ssm_state) for decoding."""
    z, xBC, dt, A, conv_state = _preprocess(p, cfg, x)
    y, h_final = ssd(xBC, dt, A, p["D"], cfg.ssm.chunk_size,
                     state=cfg.ssm.state_dim, intra_dtype=_intra_dtype(cfg),
                     out_dtype=dtype_of(cfg.compute_dtype))
    return _finish(p, cfg, y, z), (conv_state, h_final)


def mamba_decode_step(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                      conv_state: jnp.ndarray, ssm_state: jnp.ndarray):
    """x: (B, 1, d); states from prefill.  Returns (y, new_conv, new_ssm)."""
    z, xBC, dt, A, new_conv = _preprocess(p, cfg, x, conv_state)
    xh, Bmat, Cmat = split_xbc(xBC, dt.shape[-1], cfg.ssm.state_dim)
    x_t = xh[:, 0]                                       # (B,nh,P)
    b_t, c_t, dt_t = Bmat[:, 0], Cmat[:, 0], dt[:, 0]
    a_t = jnp.exp(dt_t * A[None, :])
    upd = jnp.einsum("bn,bhp,bh->bhnp", b_t, x_t.astype(jnp.float32), dt_t)
    h = ssm_state * a_t[..., None, None] + upd
    y_t = jnp.einsum("bn,bhnp->bhp", c_t, h) + \
        p["D"][None, :, None] * x_t.astype(jnp.float32)
    y = _finish(p, cfg, y_t[:, None], z)
    return y, new_conv, h.astype(jnp.float32)
