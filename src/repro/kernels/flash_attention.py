"""Fused Pallas TPU flash attention, forward and backward.

The mathematics is ``repro.models.flash.flash_attention_xla``'s, per
query block and key block of one head (s the scaled scores, masked):

    forward   m, l, acc online over the key blocks;  o = acc / l,
              lse = m + log l                       per query row, f32
    backward  p = exp(s - lse),  D_i = sum_d do_i o_i
              dv = p^T do,  ds = p (do v^T - D) scale,  dk = ds^T q,
              dq = ds k

Three kernels under one ``jax.custom_vjp``: the forward, with the
online-softmax state in VMEM across the key blocks; a dk/dv kernel with
a key block outer and the query blocks (of every query head of its KV
head) inner; and a dq kernel with a query block outer.  The backward
kernels recompute p from q, k and ``lse``; the dk/dv kernel works on the
transposed scores (keys x queries), so that ``lse`` and D are rows and
every matmul is a plain or a right-transposed one.

Layout: the model's (B, S, H, D), read as the free view (B, S, H * D);
a block is (block, D) of one head, at lanes h * D, so nothing is
transposed or copied in HBM.  GQA reads KV head h // G.  ``lse`` and D
are (B, H, 1, S) f32 rows.

Precision is the XLA path's: matmul operands stay in their input dtype
(p and ds rounded to it before their dots), every dot accumulates in
f32, and the softmax, ``lse`` and the accumulators are f32.

Causal (and windowed) attention skips key blocks no query of the block
can see: no compute (``pl.when``), and the block index map clamps to the
last block needed, so the pipeline issues no DMA for them.  Blocks the
diagonal or a window edge crosses are masked; the others are not.
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

NEG_INF = -1e30
_LANES = 128
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))          # a @ b^T
_NN = (((1,), (0,)), ((), ()))          # a @ b


def block_size(S: int) -> int:
    """The sequence block the kernels take by default: the largest of
    512, 256 and 128 rows that divides S, else 128."""
    return next((b for b in (512, 256) if S % b == 0), _LANES)


def tiles(Sq: int, Sk: int, D: int) -> bool:
    """Whether the compiled kernels take these shapes unpadded: whole
    blocks of a multiple of 128 rows, and heads of whole lane tiles."""
    return Sq % _LANES == 0 and Sk % _LANES == 0 and D % _LANES == 0


class _Cfg(NamedTuple):
    heads: int
    group: int               # query heads per KV head
    d: int
    causal: bool
    window: int              # <= 0: none
    softcap: float           # <= 0: none
    scale: float
    bq: int
    bk: int
    k_len: int               # keys at or past it are padding
    interpret: bool


# --------------------------------------------------------------------------
# which blocks hold unmasked pairs


def _visible(cfg: _Cfg, qi, ki):
    """Whether some query of block qi may see some key of block ki."""
    q0, k0 = qi * cfg.bq, ki * cfg.bk
    ok = k0 < cfg.k_len
    if cfg.causal:
        ok = ok & (k0 <= q0 + cfg.bq - 1)
    if cfg.window > 0:
        ok = ok & (q0 - (k0 + cfg.bk - 1) < cfg.window)
    return ok


def _whole(cfg: _Cfg, qi, ki):
    """Whether every query of block qi sees every key of block ki."""
    q0, k0 = qi * cfg.bq, ki * cfg.bk
    ok = k0 + cfg.bk <= cfg.k_len
    if cfg.causal:
        ok = ok & (k0 + cfg.bk - 1 <= q0)
    if cfg.window > 0:
        ok = ok & (q0 + cfg.bq - 1 - k0 < cfg.window)
    return ok


def _mask(cfg: _Cfg, qi, ki, transposed: bool = False):
    """The (bq, bk) pairs that count, or (bk, bq) where ``transposed``."""
    shape = (cfg.bk, cfg.bq) if transposed else (cfg.bq, cfg.bk)
    q = qi * cfg.bq + lax.broadcasted_iota(jnp.int32, shape,
                                           0 if not transposed else 1)
    k = ki * cfg.bk + lax.broadcasted_iota(jnp.int32, shape,
                                           1 if not transposed else 0)
    ok = k < cfg.k_len
    if cfg.causal:
        ok = ok & (q >= k)
    if cfg.window > 0:
        ok = ok & (q - k < cfg.window)
    return ok


def _k_range(cfg: _Cfg, qi, nk: int):
    """The first and last key blocks query block qi needs."""
    last = (cfg.k_len - 1) // cfg.bk
    if cfg.causal:
        last = jnp.minimum(last, (qi * cfg.bq + cfg.bq - 1) // cfg.bk)
    first = 0
    if cfg.window > 0:
        first = jnp.maximum(qi * cfg.bq - cfg.window + 1, 0) // cfg.bk
    return jnp.minimum(first, nk - 1), last


def _q_range(cfg: _Cfg, ki, nq: int):
    """The first and last query blocks that key block ki needs."""
    first = (ki * cfg.bk) // cfg.bq if cfg.causal else 0
    last = nq - 1
    if cfg.window > 0:
        last = jnp.minimum(last, (ki * cfg.bk + cfg.bk + cfg.window - 2)
                           // cfg.bq)
    return jnp.minimum(first, nq - 1), last


def _clamp(i, lo_hi):
    lo, hi = lo_hi
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _run(cfg: _Cfg, qi, ki, step):
    """``step(masked)`` where block (qi, ki) holds pairs that count,
    with the mask only where some do not."""
    vis, whole = _visible(cfg, qi, ki), _whole(cfg, qi, ki)
    pl.when(vis & whole)(lambda: step(False))
    pl.when(vis & jnp.logical_not(whole))(lambda: step(True))


def _scores(cfg: _Cfg, a, b):
    """(a @ b^T) * scale in f32, soft-capped; and the cap's tanh."""
    s = lax.dot_general(a, b, _NT, preferred_element_type=_F32) * cfg.scale
    if cfg.softcap > 0.0:
        t = jnp.tanh(s / cfg.softcap)
        return t * cfg.softcap, t
    return s, None


def _row(col, n: int):
    """(n, 1) -> (1, n) through one aligned transpose."""
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[0:1, :]


def _col(row, n: int):
    """(1, n) -> (n, 1) through one aligned transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, 0:1]


# --------------------------------------------------------------------------
# kernels


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                cfg: _Cfg):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        v = v_ref[0]
        s, _ = _scores(cfg, q_ref[0], k_ref[0])
        if masked:
            s = jnp.where(_mask(cfg, qi, ki), s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=_F32)
        m_sc[...] = m_new

    _run(cfg, qi, ki, step)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _row(m_sc[...] + jnp.log(l), cfg.bq)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, cfg: _Cfg):
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    nq = pl.num_programs(4)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked: bool):
        q, do = q_ref[0], do_ref[0]
        st, t = _scores(cfg, k_ref[0], q)                 # (bk, bq)
        if masked:
            st = jnp.where(_mask(cfg, qi, ki, transposed=True), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0])
        dv_sc[...] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                      preferred_element_type=_F32)
        dpt = lax.dot_general(v_ref[0], do, _NT, preferred_element_type=_F32)
        dst = pt * (dpt - di_ref[0, 0])
        if t is not None:
            dst = dst * (1.0 - jnp.square(t))
        dst = dst * cfg.scale
        dk_sc[...] += lax.dot_general(dst.astype(q.dtype), q, _NN,
                                      preferred_element_type=_F32)

    _run(cfg, qi, ki, step)

    @pl.when((g == cfg.group - 1) & (qi == nq - 1))
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc,
               lse_sc, di_sc, *, cfg: _Cfg):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        lse_sc[...] = _col(lse_ref[0, 0], cfg.bq)
        di_sc[...] = _col(di_ref[0, 0], cfg.bq)

    def step(masked: bool):
        k = k_ref[0]
        s, t = _scores(cfg, q_ref[0], k)                  # (bq, bk)
        if masked:
            s = jnp.where(_mask(cfg, qi, ki), s, NEG_INF)
        p = jnp.exp(s - lse_sc[...])
        dp = lax.dot_general(do_ref[0], v_ref[0], _NT,
                             preferred_element_type=_F32)
        ds = p * (dp - di_sc[...])
        if t is not None:
            ds = ds * (1.0 - jnp.square(t))
        ds = ds * cfg.scale
        dq_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                      preferred_element_type=_F32)

    _run(cfg, qi, ki, step)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# calls


def _forward(cfg: _Cfg, q, k, v):
    """Grid (batch, head, query block, key block), keys innermost."""
    B, Sq, _ = q.shape
    nq, nk = Sq // cfg.bq, k.shape[1] // cfg.bk
    G, D = cfg.group, cfg.d

    def kv_map(b, h, qi, ki):
        return b, _clamp(ki, _k_range(cfg, qi, nk)), h // G

    q_blk = pl.BlockSpec((1, cfg.bq, D), lambda b, h, qi, ki: (b, qi, h))
    kv_blk = pl.BlockSpec((1, cfg.bk, D), kv_map)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=(B, cfg.heads, nq, nk),
        in_specs=[q_blk, kv_blk, kv_blk],
        out_specs=[q_blk,
                   pl.BlockSpec((1, 1, 1, cfg.bq),
                                lambda b, h, qi, ki: (b, h, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, cfg.heads, 1, Sq), _F32)],
        scratch_shapes=[pltpu.VMEM((cfg.bq, 1), _F32),
                        pltpu.VMEM((cfg.bq, 1), _F32),
                        pltpu.VMEM((cfg.bq, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
    )(q, k, v)


def _backward(cfg: _Cfg, res, do):
    q, k, v, o, lse = res
    B, Sq, _ = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // cfg.bq, Sk // cfg.bk
    G, D, H = cfg.group, cfg.d, cfg.heads
    di = jnp.einsum("bshd,bshd->bhs",
                    do.reshape(B, Sq, H, D).astype(_F32),
                    o.reshape(B, Sq, H, D).astype(_F32))[:, :, None, :]

    # dk, dv: grid (batch, KV head, key block, group, query block)
    def q_map(b, kh, ki, g, qi):
        return b, _clamp(qi, _q_range(cfg, ki, nq)), kh * G + g

    def row_map(b, kh, ki, g, qi):
        return b, kh * G + g, 0, _clamp(qi, _q_range(cfg, ki, nq))

    q_blk = pl.BlockSpec((1, cfg.bq, D), q_map)
    kv_blk = pl.BlockSpec((1, cfg.bk, D), lambda b, kh, ki, g, qi: (b, ki, kh))
    row_blk = pl.BlockSpec((1, 1, 1, cfg.bq), row_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg),
        grid=(B, H // G, nk, G, nq),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, row_blk, row_blk],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((cfg.bk, D), _F32),
                        pltpu.VMEM((cfg.bk, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=cfg.interpret,
    )(q, k, v, do, lse, di)

    # dq: grid (batch, head, query block, key block)
    def kv_map(b, h, qi, ki):
        return b, _clamp(ki, _k_range(cfg, qi, nk)), h // G

    q_blk = pl.BlockSpec((1, cfg.bq, D), lambda b, h, qi, ki: (b, qi, h))
    kv_blk = pl.BlockSpec((1, cfg.bk, D), kv_map)
    row_blk = pl.BlockSpec((1, 1, 1, cfg.bq),
                           lambda b, h, qi, ki: (b, h, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg),
        grid=(B, H, nq, nk),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, row_blk, row_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.bq, D), _F32),
                        pltpu.VMEM((cfg.bq, 1), _F32),
                        pltpu.VMEM((cfg.bq, 1), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(cfg: _Cfg, q, k, v):
    return _forward(cfg, q, k, v)[0]


def _attend_fwd(cfg: _Cfg, q, k, v):
    o, lse = _forward(cfg, q, k, v)
    return o, (q, k, v, o, lse)


def _attend_bwd(cfg: _Cfg, res, do):
    return _backward(cfg, res, do)


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q (B, Sq, H, D); k, v (B, Sk, KVH, D), H a multiple of KVH.
    Returns (B, Sq, H, D) in q's dtype, differentiable in q, k and v.

    ``window`` <= 0 means none; ``softcap`` <= 0 none; ``scale``
    multiplies the f32 scores, 1/sqrt(D) where None.  Blocks default to
    ``block_size``; Sq and Sk are padded to whole blocks (padded keys
    masked), which copies: the model calls it only where ``tiles``.
    ``interpret=None`` follows the backend."""
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    bq = min(block_q or block_size(Sq), max(Sq, 8))
    bk = min(block_k or block_size(Sk), max(Sk, 8))
    pq, pk = -Sq % bq, -Sk % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    cfg = _Cfg(heads=H, group=H // KVH, d=D, causal=causal,
               window=int(window or 0), softcap=float(softcap),
               scale=float(D ** -0.5 if scale is None else scale),
               bq=bq, bk=bk, k_len=Sk,
               interpret=interpret_mode() if interpret is None else interpret)
    o = _attend(cfg, q.reshape(B, Sq + pq, H * D),
                k.reshape(B, Sk + pk, KVH * D), v.reshape(B, Sk + pk, KVH * D))
    return o.reshape(B, Sq + pq, H, D)[:, :Sq]
