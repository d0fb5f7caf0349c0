"""Fused Pallas TPU kernels for the Mamba2 SSD scan, forward and backward.

The mathematics is ``repro.models.ssm.ssd_chunked``'s, per chunk of L
steps and head h (cum = the within-chunk cumsum of dt * A):

    G      = C B^T                                  (L, L), shared by heads
    M_ij   = G_ij * exp(cum_i - cum_j) * dt_j       for j <= i, else 0
    y      = M x + exp(cum) * (C h_prev) + D x
    h_next = exp(cum_L) * h_prev + B^T (w * x),     w_j = exp(cum_L - cum_j) dt_j

The forward kernel walks the chunks in order with the inter-chunk state
in VMEM and writes ``y`` and ``h_final`` once; the (L, L) decay and M
never leave VMEM.  The backward kernel walks them in reverse with the
state's cotangent in VMEM.  ``ssd_pallas`` ties the two together under a
``jax.custom_vjp``.

Layout: lanes-major, (channels, steps), the layout XLA gives the conv
output [x | B | C] around the scan, so nothing is copied or transposed
in HBM on the way in or out:

* x^T, y^T, dx^T (b, nh*P, S) in blocks of (hb*P, L): hb heads a cell,
  each head a block of P rows;
* B^T, C^T       (N, L) blocks of the same conv output, read in place; G
  is computed once per block and reused for every head (``ngroups`` is
  1);
* dt, cum        (L,) per head, as rows (hb, L); cum as a column for the
  (L, L) decay is the one lane broadcast a head needs;
* states         (b, [nc,] nh*P, N) f32, each head's (N, P) state
  transposed.

Per-head scalings by a function of the step are then row broadcasts, and
per-head sums over the head dimension are sums over rows.

Matmul operands are rounded to bf16 on the TPU, which is what XLA's
DEFAULT precision does with the f32 einsums of ``ssd_chunked`` there;
accumulation is f32.  In interpret mode (CPU) they stay f32, as DEFAULT
means there.  Elementwise math and the inter-chunk states are f32, and
``intra_dtype`` rounds the same three terms of y as ``ssd_chunked``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

_LANES = 128
_ROWS = 16                  # sublanes of a bf16 tile
_CELL_ROWS = 1024           # most rows (hb * P) one grid cell takes
_STRIP = 128                # rows of the forward's (L, L) work at a time
_F32 = jnp.float32


def heads_per_cell(nh: int, P: int) -> int:
    """Heads per grid cell: the most whose rows fit ``_CELL_ROWS``."""
    return max(hb for hb in range(1, nh + 1)
               if nh % hb == 0 and hb * P <= max(_CELL_ROWS, P))


def tiles(S: int, nh: int, P: int, N: int, chunk: int) -> bool:
    """Whether the kernel takes these shapes: whole chunks of a multiple
    of 128 steps; heads and state of whole bf16 tiles, the state dividing
    the heads' rows so that B and C are blocks of the conv output."""
    L = min(chunk, S)
    return (S % L == 0 and L % _LANES == 0 and P % _ROWS == 0
            and N % _ROWS == 0 and (nh * P) % N == 0)


class _Cfg(NamedTuple):
    L: int
    P: int
    nh: int                  # heads
    hb: int                  # heads per cell
    nc: int                  # chunks
    intra: str               # intra_dtype's name
    mm: str                  # matmul operand dtype's name
    y: str                   # y's dtype's name
    interpret: bool


def _dot(a, b, cfg: _Cfg, ta: bool = False, tb: bool = False):
    """a @ b with either side transposed, f32 accumulation."""
    mm = jnp.dtype(cfg.mm)
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return lax.dot_general(a.astype(mm), b.astype(mm), dims,
                           preferred_element_type=_F32)


def _strips(L: int):
    """Row strips (r0, c1) of an (L, L) matrix: rows r0..r0+_STRIP and the
    columns 0..c1 that can hold entries on or below the diagonal; those
    right of c1 are zeros and are skipped."""
    return [(r0, min(L, -(-(r0 + _STRIP) // _LANES) * _LANES))
            for r0 in range(0, L, _STRIP)]


def _decay(col, cumr_h, r0: int, c1: int):
    """exp(cum_i - cum_j) for rows i from r0 and columns j < c1, 0 above
    the diagonal.  ``col`` holds cum_i over 128 lanes.  The exponent is
    masked, not the product: above the diagonal it is positive and grows
    with the chunk, so exp overflows and inf * 0 is NaN."""
    seg = jnp.concatenate([col - cumr_h[:, t:t + _LANES]
                           for t in range(0, c1, _LANES)], axis=1)
    tri = (lax.broadcasted_iota(jnp.int32, seg.shape, 0) + r0
           >= lax.broadcasted_iota(jnp.int32, seg.shape, 1))
    return jnp.exp(jnp.where(tri, seg, -jnp.inf))


def _chunk_terms(cum_ref, dt_ref, L: int):
    """Per head, as rows (hb, L): cum, dt, the state's input weights
    w_j = exp(cum_L - cum_j) dt_j with their decay, and exp(cum); cum as
    columns (L, hb); exp(cum_L) (hb, 1)."""
    cumr, dtr = cum_ref[0, 0, 0], dt_ref[0, 0, 0]
    last = cumr[:, L - 1:L]
    wdecay = jnp.exp(last - cumr)
    return (cumr, dtr, cumr.T, wdecay, wdecay * dtr, jnp.exp(cumr),
            jnp.exp(last))


def _cum_column(cumc, h: int, L: int):
    return jnp.broadcast_to(cumc[:, h:h + 1], (L, _LANES))


def _over_lanes(col, n: int):
    """Columns (r, 1) -> (r, n), as its own lane broadcast: a (1, 1)
    value may not be broadcast over sublanes and lanes at once."""
    lane = lax.broadcasted_iota(jnp.int32, (col.shape[0], n), 1)
    return jnp.where(lane >= 0, col, 0.0)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref,
                hfin_ref, *rest, cfg: _Cfg, save: bool):
    """One (batch, head group, chunk) cell, chunks in order."""
    hs_ref = rest[0] if save else None
    h_scr, g_scr = rest[-2], rest[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    L, P, idt = cfg.L, cfg.P, jnp.dtype(cfg.intra)
    BT, CT = b_ref[0].astype(_F32), c_ref[0].astype(_F32)  # (N, L)
    Bm = BT.T
    g_scr[...] = _dot(CT.T, BT, cfg)                      # G = C B^T
    cumr, dtr, cumc, _, w, ecum, elast = _chunk_terms(cum_ref, dt_ref, L)
    elast = _over_lanes(elast, BT.shape[0])
    for h in range(cfg.hb):
        r = slice(h * P, (h + 1) * P)
        xT = x_ref[0, r, :]                               # (P, L)
        xf = xT.astype(_F32)
        h_prev = h_scr[r, :]                              # (P, N)
        if save:
            hs_ref[0, 0, r, :] = h_prev
        col = _cum_column(cumc, h, L)
        y_inter = _dot(h_prev, CT, cfg) * ecum[h:h + 1]   # (P, L)
        y_skip = d_ref[0, h:h + 1] * xf
        for r0, c1 in _strips(L):
            rows = slice(r0, r0 + _STRIP)
            m = (g_scr[rows, :c1] * _decay(col[rows], cumr[h:h + 1], r0, c1)
                 * dtr[h:h + 1, :c1])
            y_intra = _dot(xT[:, :c1].astype(idt), m.astype(idt), cfg,
                           tb=True)                       # (M x)^T
            y = (y_intra.astype(idt) + y_inter[:, rows].astype(idt)
                 + y_skip[:, rows].astype(idt))
            y_ref[0, r, rows] = y.astype(y_ref.dtype)
        h_new = h_prev * elast[h:h + 1] + _dot(xf * w[h:h + 1], Bm, cfg)
        h_scr[r, :] = h_new

        @pl.when(c == cfg.nc - 1)
        def _():
            hfin_ref[0, r, :] = h_new


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, hs_ref,
                dy_ref, dhfin_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                dcum_ref, dd_ref, dh_scr, g_scr, dg_scr, *, cfg: _Cfg):
    """One (batch, chunk, head group) cell, chunks in reverse.  dB^T and
    dC^T sum over the head groups in their resident output blocks."""
    hg = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)                       # the last chunk
    def _():
        dh_scr[hg] = dhfin_ref[0]

    @pl.when(hg == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    L, P, idt = cfg.L, cfg.P, jnp.dtype(cfg.intra)
    BT, CT = b_ref[0].astype(_F32), c_ref[0].astype(_F32)  # (N, L)
    Cm = CT.T
    g_scr[...] = _dot(Cm, BT, cfg)                        # G = C B^T
    dg_scr[...] = jnp.zeros_like(dg_scr)
    cumr, dtr, cumc, wdecay, w, ecum, elast = _chunk_terms(cum_ref, dt_ref, L)
    elast_n = _over_lanes(elast, BT.shape[0])
    at_last = lax.broadcasted_iota(jnp.int32, (1, L), 1) == L - 1
    ddt_rows, dcum_rows, dcum_cols, dd_rows = [], [], [], []
    dbT = dcT = jnp.zeros(BT.shape, _F32)
    for h in range(cfg.hb):
        r = slice(h * P, (h + 1) * P)
        xT = x_ref[0, r, :]                               # (P, L)
        xf, dy = xT.astype(_F32), dy_ref[0, r, :].astype(_F32)
        h_prev, dh = hs_ref[0, 0, r, :], dh_scr[hg, r, :]  # (P, N)
        w_h, e_h = w[h:h + 1], ecum[h:h + 1]              # (1, L)
        # the state's input weights and decay, and y_inter
        bdh = _dot(dh, BT, cfg)                           # (B dh)^T
        dw = jnp.sum(xf * bdh, axis=0, keepdims=True)
        dwx = dw * w_h
        dlast = jnp.sum(dwx) + elast[h:h + 1] * jnp.sum(h_prev * dh)
        dcum = (jnp.sum(dy * _dot(h_prev, CT, cfg), axis=0, keepdims=True)
                * e_h - dwx + jnp.where(at_last, dlast, 0.0))
        dye = dy * e_h
        dbT = dbT + _dot(dh, xf * w_h, cfg, ta=True)
        dcT = dcT + _dot(h_prev, dye, cfg, ta=True)
        dh_scr[hg, r, :] = dh * elast_n[h:h + 1] + _dot(dye, Cm, cfg)
        # the intra-chunk M, whole: in strips it would take more matmul
        # passes and spill no less
        dec = _decay(_cum_column(cumc, h, L), cumr[h:h + 1], 0, L)
        dt_j = dtr[h:h + 1]
        g = g_scr[...]
        m = (g * dec * dt_j).astype(idt)
        dx = w_h * bdh + d_ref[0, h:h + 1] * dy + _dot(dy, m, cfg)
        dx_ref[0, r, :] = dx.astype(dx_ref.dtype)         # (M^T dy)^T, ...
        e = _dot(dy, xT, cfg, ta=True) * dec              # dM * decay
        dg_scr[...] += e * dt_j
        rr = e * g
        cs = jnp.sum(rr, axis=0, keepdims=True)
        ddt_rows.append(dw * wdecay[h:h + 1] + cs)
        dcum_rows.append(dcum - dt_j * cs)
        dcum_cols.append(jnp.sum(rr * dt_j, axis=1, keepdims=True))
        dd_rows.append(jnp.sum(xf * dy, axis=0, keepdims=True))
    dG = dg_scr[...]
    dc_ref[0] += dcT + _dot(BT, dG, cfg, tb=True)         # (dG B)^T
    db_ref[0] += dbT + _dot(CT, dG, cfg)                  # (dG^T C)^T
    ddt_ref[0, 0, 0] = jnp.concatenate(ddt_rows, axis=0)
    dcum_ref[0, 0, 0] = (jnp.concatenate(dcum_rows, axis=0)
                         + jnp.concatenate(dcum_cols, axis=1).T)
    dd_ref[0, 0, 0] = jnp.concatenate(dd_rows, axis=0)


def _forward(cfg: _Cfg, save: bool, xbc, dt, cum, d):
    """Grid (batch, head group, chunk), chunks innermost and in order."""
    b, _, S = xbc.shape
    L, hbP, rows = cfg.L, cfg.hb * cfg.P, cfg.nh * cfg.P
    N = (xbc.shape[1] - rows) // 2
    head_blk = pl.BlockSpec((1, hbP, L), lambda i, g, c: (i, g, c))
    row_blk = pl.BlockSpec((1, 1, 1, cfg.hb, L),
                           lambda i, g, c: (i, c, g, 0, 0))
    state_blk = pl.BlockSpec((1, hbP, N), lambda i, g, c: (i, g, 0))
    out_specs = [head_blk, state_blk]
    out_shape = [jax.ShapeDtypeStruct((b, rows, S), jnp.dtype(cfg.y)),
                 jax.ShapeDtypeStruct((b, rows, N), _F32)]
    if save:       # each chunk's starting state, for the backward
        out_specs.append(pl.BlockSpec((1, 1, hbP, N),
                                      lambda i, g, c: (i, c, g, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, cfg.nc, rows, N), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, save=save),
        grid=(b, cfg.nh // cfg.hb, cfg.nc),
        in_specs=[head_blk,
                  pl.BlockSpec((1, N, L), lambda i, g, c: (i, rows // N, c)),
                  pl.BlockSpec((1, N, L),
                               lambda i, g, c: (i, rows // N + 1, c)),
                  row_blk, row_blk,
                  pl.BlockSpec((1, cfg.hb, L), lambda i, g, c: (g, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hbP, N), _F32), pltpu.VMEM((L, L), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
    )(xbc, xbc, xbc, dt, cum, d)


def _backward(cfg: _Cfg, res, dy, dhfin):
    """Grid (batch, chunk, head group), chunks in reverse, head groups
    innermost so that dB and dC add up in their output blocks."""
    xbc, dt, cum, d, hs = res
    b, _, S = xbc.shape
    L, hbP, rows, ng = cfg.L, cfg.hb * cfg.P, cfg.nh * cfg.P, cfg.nh // cfg.hb
    N = (xbc.shape[1] - rows) // 2

    def rev(c):
        return cfg.nc - 1 - c

    head_blk = pl.BlockSpec((1, hbP, L), lambda i, c, g: (i, g, rev(c)))
    state_blk = pl.BlockSpec((1, N, L), lambda i, c, g: (i, 0, rev(c)))
    row_blk = pl.BlockSpec((1, 1, 1, cfg.hb, L),
                           lambda i, c, g: (i, rev(c), g, 0, 0))
    dx, dBT, dCT, ddt, dcum, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg),
        grid=(b, cfg.nc, ng),
        in_specs=[head_blk,
                  pl.BlockSpec((1, N, L),
                               lambda i, c, g: (i, rows // N, rev(c))),
                  pl.BlockSpec((1, N, L),
                               lambda i, c, g: (i, rows // N + 1, rev(c))),
                  row_blk, row_blk,
                  pl.BlockSpec((1, cfg.hb, L), lambda i, c, g: (g, 0, 0)),
                  pl.BlockSpec((1, 1, hbP, N),
                               lambda i, c, g: (i, rev(c), g, 0)),
                  head_blk,
                  pl.BlockSpec((1, hbP, N), lambda i, c, g: (i, g, 0))],
        out_specs=[head_blk, state_blk, state_blk, row_blk, row_blk,
                   row_blk],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, xbc.dtype),
                   jax.ShapeDtypeStruct((b, N, S), _F32),
                   jax.ShapeDtypeStruct((b, N, S), _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(cum.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((ng, hbP, N), _F32),
                        pltpu.VMEM((L, L), _F32), pltpu.VMEM((L, L), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=cfg.interpret,
    )(xbc, xbc, xbc, dt, cum, d, hs, dy, dhfin)
    # x, B and C came in as one array: so goes their gradient
    dxbc = jnp.concatenate([dx, dBT.astype(dx.dtype), dCT.astype(dx.dtype)],
                           axis=1)
    return dxbc, ddt, dcum, jnp.sum(dd, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(cfg: _Cfg, xbc, dt, cum, d):
    y, hfin = _forward(cfg, False, xbc, dt, cum, d)
    return y, hfin


def _scan_fwd(cfg: _Cfg, xbc, dt, cum, d):
    y, hfin, hs = _forward(cfg, True, xbc, dt, cum, d)
    return (y, hfin), (xbc, dt, cum, d, hs)


def _scan_bwd(cfg: _Cfg, res, cts):
    with jax.named_scope("ssd"):
        return _backward(cfg, res, *cts)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_pallas(xbc, dt, A, D, chunk: int, *, state: int, intra_dtype=_F32,
               out_dtype=None, interpret: Optional[bool] = None):
    """The SSD scan through the fused kernels, differentiable in every
    input.  ``xbc`` (b, nh*P + 2N, S) is the conv output [x | B | C]
    channels-major, the layout XLA gives it; dt (b, S, nh); A, D (nh,);
    ``state`` is N.  Returns y (b, S, nh, P) in ``out_dtype`` (default
    ``intra_dtype``) and h_final (b, nh, N, P) f32, as ``ssd_chunked``.
    The shapes must pass ``tiles``; ``interpret=None`` follows the
    backend."""
    b, S, nh = dt.shape
    N, rows = state, xbc.shape[1] - 2 * state
    P = rows // nh
    if not tiles(S, nh, P, N, chunk):
        raise ValueError(f"the SSD kernel does not tile S={S} nh={nh} "
                         f"P={P} N={N} chunk={chunk}")
    L = min(chunk, S)
    nc, hb = S // L, heads_per_cell(nh, P)
    if interpret is None:
        interpret = interpret_mode()
    cfg = _Cfg(L=L, P=P, nh=nh, hb=hb, nc=nc,
               intra=jnp.dtype(intra_dtype).name,
               mm="float32" if interpret else "bfloat16",
               y=jnp.dtype(out_dtype or intra_dtype).name,
               interpret=interpret)

    def per_head_rows(a):                         # (b, nc, groups, hb, L)
        return a.reshape(b, nc, L, nh // hb, hb).transpose(0, 1, 3, 4, 2)

    dt_rows = per_head_rows(dt.astype(_F32))
    # the within-chunk cumsum as one matmul with a triangle of ones, in
    # f32: XLA's cumsum lowers to a slow reduce-window on the TPU
    dA = dt_rows * per_head_rows(jnp.broadcast_to(A.astype(_F32), dt.shape))
    cum = jnp.matmul(dA, jnp.triu(jnp.ones((L, L), _F32)),
                     precision=lax.Precision.HIGHEST)
    d_rows = jnp.broadcast_to(D.astype(_F32).reshape(nh // hb, hb, 1),
                              (nh // hb, hb, L))
    yT, hfin = _scan(cfg, xbc, dt_rows, cum, d_rows)
    return (yT.transpose(0, 2, 1).reshape(b, S, nh, P),
            hfin.reshape(b, nh, P, N).transpose(0, 1, 3, 2))
