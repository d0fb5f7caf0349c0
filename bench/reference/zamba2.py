"""Plain float32 reference of a Zamba2 hybrid language model (HF
``Zyphra/Zamba2-1.2B``, arXiv:2411.15242), as the configuration file
states it, and its FLOP count.

The weights are the benchmark's, made here from the seed with HF Zamba2's
initialisation, in the tree the program's training step takes:
``{"embedding": {"embed"}, "stack": {"mamba": {...}, "shared": {...},
"sites": {...}, "ln_f"}}``, Mamba leaves stacked over the layers and the
sites' adapters and linears over the sites.  Norm weights are stored as
(gain - 1), so zeros are HF's ones.

For the embedding output e and k = ``shared_attn_every``, layer j is

    mamba layer:   x = x + mamba_block(rms_norm(x))
    hybrid layer:  t = shared(x, e, i) @ L_i          (j = (i + 1) k < n_layers)
                   x = x + mamba_block(rms_norm(x + t))
    shared(x, e, i):
        u = rms_norm([x | e])                            2 d wide
        q, k, v = rope(u @ Wq), rope(u @ Wk), u @ Wv     heads of head_dim
        a = causal_softmax(q k^T (head_dim / 2)^-0.5) v @ Wo
        m = rms_norm(a)                                  no residual in the block
        g | h = m @ W_gate_up + (m @ A_i) @ B_i
        return (gelu_erf(g) * h) @ W_down

with ``mamba_block`` the Mamba2 mixer of ``reference/mamba2.py`` (SSD in
its dual form).  Attention is unfused, a block of query rows at a time.
The stack ends with rms_norm(ln_f) and logits against the tied embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference import mamba2
from reference.common import (Dot, cross_entropy_loss, inv_softplus, normal,
                              rms_norm)

INIT_STD = 0.02         # HF Zamba2's initializer_range
DT_FLOOR = 1e-4         # HF Zamba2's time_step_floor
SCORE_BYTES = 1 << 27   # one block of f32 attention scores


def hybrid_ids(cfg: dict) -> list:
    k = cfg["shared_attn_every"]
    return list(range(k, cfg["n_layers"], k))


# ----------------------------------------------------------------- init

def init_layers(cfg: dict, key, n_layers: int) -> dict:
    s = cfg["ssm"]
    d = cfg["d_model"]
    d_inner, nh, _, n = mamba2.dims(cfg)
    conv_ch = d_inner + 2 * n

    def layer(k):
        ks = jax.random.split(k, 4)
        dt = jnp.exp(jax.random.uniform(
            ks[2], (nh,), minval=jnp.log(s["dt_min"]),
            maxval=jnp.log(s["dt_max"])))
        dt = jnp.maximum(dt, DT_FLOOR)
        return {
            "in_proj": normal(ks[0], (d, 2 * d_inner + 2 * n + nh), INIT_STD),
            "conv_w": normal(ks[1], (s["conv_width"], conv_ch), INIT_STD),
            "conv_b": jnp.zeros((conv_ch,), jnp.float32),
            "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
            "D": jnp.ones((nh,), jnp.float32),
            "dt_bias": inv_softplus(dt),
            "norm": jnp.zeros((d_inner,), jnp.float32),
            "out_proj": normal(ks[3], (d_inner, d), INIT_STD),
            "in_norm": jnp.zeros((d,), jnp.float32),
        }

    return jax.vmap(layer)(jax.random.split(key, n_layers))


def init_shared(cfg: dict, key) -> dict:
    d, ff = cfg["d_model"], cfg["d_ff"]
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    ks = jax.random.split(key, 6)
    return {
        "ln_attn": jnp.zeros((2 * d,), jnp.float32),
        "attn": {"wq": normal(ks[0], (2 * d, h, hd), INIT_STD),
                 "wk": normal(ks[1], (2 * d, kvh, hd), INIT_STD),
                 "wv": normal(ks[2], (2 * d, kvh, hd), INIT_STD),
                 "wo": normal(ks[3], (h, hd, d), INIT_STD)},
        "ln_ff": jnp.zeros((d,), jnp.float32),
        "mlp": {"w_gate_up": normal(ks[4], (d, 2, ff), INIT_STD),
                "w2": normal(ks[5], (ff, d), INIT_STD)},
    }


def init_sites(cfg: dict, key) -> dict:
    d, ff, r = cfg["d_model"], cfg["d_ff"], cfg["adapter_rank"]
    n = len(hybrid_ids(cfg))
    ks = jax.random.split(key, 3)
    return {"adapter_a": normal(ks[0], (n, d, r), INIT_STD),
            "adapter_b": normal(ks[1], (n, r, 2, ff), INIT_STD),
            "linear": normal(ks[2], (n, d, d), INIT_STD)}


def init(cfg: dict, key) -> dict:
    k_embed, k_layers, k_shared, k_sites = jax.random.split(key, 4)
    d = cfg["d_model"]
    return {"embedding": {"embed": normal(k_embed, (cfg["vocab_size"], d),
                                          INIT_STD)},
            "stack": {"mamba": init_layers(cfg, k_layers, cfg["n_layers"]),
                      "shared": init_shared(cfg, k_shared),
                      "sites": init_sites(cfg, k_sites),
                      "ln_f": jnp.zeros((d,), jnp.float32)}}


# -------------------------------------------------------------- forward

def rope(x, theta: float):
    """x (B, S, H, D): the halves rotated by position s at frequencies
    theta^(-j / (D/2)), as HF's rotate_half form."""
    seq, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def query_rows(batch: int, heads: int, seq: int) -> int:
    """Query rows per block such that one (B, H, rows, S) f32 block of
    scores stays under ``SCORE_BYTES``; a divisor of ``seq``."""
    rows = max(1, min(seq, SCORE_BYTES // max(batch * heads * seq * 4, 1)))
    while seq % rows:
        rows -= 1
    return rows


def attention(q, k, v, scale: float, dot: Dot):
    """Causal softmax attention, unfused; q, k, v (B, S, H, D)."""
    bsz, seq, h, hd = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    rows = query_rows(bsz, h, seq)

    @jax.checkpoint
    def block(args):
        q_blk, first = args                       # (B, rows, H, D)
        s = dot("bqhd,bkhd->bhqk", q_blk, k) * scale
        pos = first + jnp.arange(rows)
        s = jnp.where(jnp.arange(seq)[None, :] <= pos[:, None], s, -jnp.inf)
        return dot("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = jnp.moveaxis(q.reshape(bsz, seq // rows, rows, h, hd), 1, 0)
    out = lax.map(block, (q_blocks, jnp.arange(0, seq, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(bsz, seq, h, hd)


def shared(p: dict, sp: dict, cfg: dict, x, e, dot: Dot):
    eps = cfg["norm_eps"]
    u = rms_norm(jnp.concatenate([x, e], axis=-1), p["ln_attn"], eps)
    attn = p["attn"]
    q = rope(dot("bsd,dhk->bshk", u, attn["wq"]), cfg["rope_theta"])
    k = rope(dot("bsd,dhk->bshk", u, attn["wk"]), cfg["rope_theta"])
    v = dot("bsd,dhk->bshk", u, attn["wv"])
    o = attention(q, k, v, (cfg["head_dim"] / 2) ** -0.5, dot)
    m = rms_norm(dot("bshk,hkd->bsd", o, attn["wo"]), p["ln_ff"], eps)
    gu = (dot("bsd,dgf->bsgf", m, p["mlp"]["w_gate_up"])
          + dot("bsr,rgf->bsgf", dot("bsd,dr->bsr", m, sp["adapter_a"]),
                sp["adapter_b"]))
    hid = jax.nn.gelu(gu[..., 0, :], approximate=False) * gu[..., 1, :]
    return dot("bsf,fd->bsd", hid, p["mlp"]["w2"])


def mamba_layer(p: dict, cfg: dict, x, dot: Dot, t=None):
    x_in = x if t is None else x + t
    y = mamba2.mamba_block(p, cfg, rms_norm(x_in, p["in_norm"],
                                            cfg["norm_eps"]), dot)
    return dot.act(x + y)


def layers(stack: dict, cfg: dict, e, dot: Dot):
    mamba, ids = stack["mamba"], hybrid_ids(cfg)

    def site_layer(x, e, sp, lp):
        t = dot("bsd,de->bse", shared(stack["shared"], sp, cfg, x, e, dot),
                sp["linear"])
        return mamba_layer(lp, cfg, x, dot, t)

    def body(x, lp):
        return mamba_layer(lp, cfg, x, dot), None

    x = e
    bounds = [0] + ids + [cfg["n_layers"]]
    for a, b in zip(bounds, bounds[1:]):
        if a in ids:
            i = ids.index(a)
            x = jax.checkpoint(site_layer)(
                x, e, jax.tree.map(lambda w: w[i], stack["sites"]),
                jax.tree.map(lambda w: w[a], mamba))
            a += 1
        if b > a:
            x, _ = lax.scan(jax.checkpoint(body), x,
                            jax.tree.map(lambda w: w[a:b], mamba))
    return x


def loss(params: dict, cfg: dict, tokens, dot: Dot):
    embed = params["embedding"]["embed"]
    x = layers(params["stack"], cfg, dot.act(embed[tokens]), dot)
    x = rms_norm(x, params["stack"]["ln_f"], cfg["norm_eps"])
    return cross_entropy_loss(x, embed, tokens, dot)


# ---------------------------------------------------------------- FLOPs

def site_flops_per_token(cfg: dict, seq: int) -> int:
    """Matmul FLOPs of one application of the shared block and the
    site's linear, per token: the q, k, v and output projections, the
    gate-up projection with the adapter, the down projection, the site's
    linear, and attention's q k^T and p v over the causal half (seq / 2
    keys a query, on average)."""
    d, ff, r = cfg["d_model"], cfg["d_ff"], cfg["adapter_rank"]
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    proj = (2 * d * h * hd + 2 * (2 * d * kvh * hd) + h * hd * d
            + d * 2 * ff + d * r + r * 2 * ff + ff * d + d * d)
    attn = 2 * (seq // 2) * h * hd
    return 2 * (proj + attn)


def flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: the forward's matmuls and
    einsums (the Mamba layers as ``reference/mamba2.py`` counts them, the
    sites as ``site_flops_per_token``, the logits), times 3 for the
    backward.  Recomputation is not counted."""
    per_token = (cfg["n_layers"] * mamba2.mamba_layer_flops_per_token(cfg)
                 + len(hybrid_ids(cfg)) * site_flops_per_token(cfg, seq))
    fwd = per_token * batch * seq + mamba2.logits_flops(cfg, batch, seq)
    return 3 * fwd
