"""Plain float32 reference of a Mamba2 (SSD) language model, as the
configuration file states it, and its FLOP count.

The weights are the benchmark's, made here from the seed in the tree the
program's training step takes: ``{"embedding": {"embed"}, "stack":
{"layers": {...}, "ln_f"}}``, each layer leaf stacked over the layers.

Block, for input x (B, S, d) and d_inner = expand * d, nh = d_inner / P:

    z, xBC, dt = split(x @ in_proj)               widths d_inner, d_inner + 2N, nh
    xBC = silu(causal depthwise conv(xBC) + conv_b)
    x_h, B, C = split(xBC)                         one B, C group for all heads
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{k=s+1..t} dt_k A) dt_s x_s + D x_t
    out = rms_norm(y * silu(z)) @ out_proj,   residual x + out

SSD is computed in its quadratic ("dual") form over the whole sequence,
not in chunks, so it shares no algorithm with the program's chunked scan.
The stack has no norm before each block and ends with rms_norm(ln_f) and
logits against the tied embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference.common import (Dot, cross_entropy_loss, fan_in_std,
                              head_chunk, inv_softplus, normal, rms_norm)

EMBED_STD = 0.02        # mamba_ssm's initializer_range for the embedding


def dims(cfg: dict):
    s = cfg["ssm"]
    d_inner = s["expand"] * cfg["d_model"]
    return d_inner, d_inner // s["head_dim"], s["head_dim"], s["state_dim"]


def init_layers(cfg: dict, key, n_layers: int) -> dict:
    s = cfg["ssm"]
    d = cfg["d_model"]
    d_inner, nh, _, n = dims(cfg)
    conv_ch = d_inner + 2 * n
    d_proj = 2 * d_inner + 2 * n + nh
    w = s["conv_width"]

    def layer(k):
        ks = jax.random.split(k, 5)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (nh,), minval=jnp.log(s["dt_min"]),
            maxval=jnp.log(s["dt_max"])))
        return {
            "in_proj": normal(ks[0], (d, d_proj), fan_in_std(d)),
            "conv_w": normal(ks[1], (w, conv_ch), fan_in_std(w)),
            "conv_b": jnp.zeros((conv_ch,), jnp.float32),
            "A_log": jnp.log(jax.random.uniform(ks[2], (nh,), minval=1.0,
                                                maxval=16.0)),
            "D": jnp.ones((nh,), jnp.float32),
            "dt_bias": inv_softplus(dt),
            "norm": jnp.zeros((d_inner,), jnp.float32),
            "out_proj": normal(ks[4], (d_inner, d),
                               fan_in_std(d_inner) * residual_scale(cfg)),
        }

    return jax.vmap(layer)(jax.random.split(key, n_layers))


def residual_scale(cfg: dict) -> float:
    """mamba_ssm's rescaled residual init: each block's output projection
    divided by sqrt(n_layers), so the residual stream stays O(1) deep in
    the stack."""
    return cfg["n_layers"] ** -0.5


def init_embedding(cfg: dict, key) -> dict:
    return {"embed": normal(key, (cfg["vocab_size"], cfg["d_model"]),
                            EMBED_STD)}


def init(cfg: dict, key) -> dict:
    k_embed, k_layers = jax.random.split(key)
    return {"embedding": init_embedding(cfg, k_embed),
            "stack": {"layers": init_layers(cfg, k_layers, cfg["n_layers"]),
                      "ln_f": jnp.zeros((cfg["d_model"],), jnp.float32)}}


def ssd(xh, b, c, dt, a, d_skip, dot: Dot):
    """xh (B, S, nh, P); b, c (B, S, N); dt (B, S, nh); a, d_skip (nh,)."""
    bsz, seq, nh, p = xh.shape
    cum = jnp.cumsum(dt * a, axis=1)                         # (B, S, nh)
    cb = dot("btn,bsn->bts", c, b)                           # (B, S, S)
    tri = jnp.tril(jnp.ones((seq, seq), bool))[None, :, :, None]
    hc = head_chunk(bsz, seq, nh)

    @jax.checkpoint
    def block(args):
        x_c, dt_c, cum_c = args          # (B, S, hc, P), (B, S, hc) x2
        seg = cum_c[:, :, None, :] - cum_c[:, None, :, :]   # (B, t, s, hc)
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        m = cb[..., None] * decay * dt_c[:, None, :, :]
        return dot("btsh,bshp->bthp", m, x_c)

    def heads_first(t):                  # (B, S, nh, ...) -> (nh/hc, B, S, hc, ...)
        t = t.reshape((bsz, seq, nh // hc, hc) + t.shape[3:])
        return jnp.moveaxis(t, 2, 0)

    y = lax.map(block, (heads_first(xh), heads_first(dt), heads_first(cum)))
    y = jnp.moveaxis(y, 0, 2).reshape(bsz, seq, nh, p)
    return y + d_skip[None, None, :, None] * xh


def mamba_block(p: dict, cfg: dict, x, dot: Dot):
    s = cfg["ssm"]
    bsz, seq, _ = x.shape
    d_inner, nh, hp, n = dims(cfg)
    zxbcdt = dot("bsd,de->bse", x, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * n:]
    w = s["conv_width"]
    xpad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(xpad[:, i:i + seq, :] * p["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(bsz, seq, nh, hp)
    b = xbc[..., d_inner:d_inner + n]
    c = xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs, b, c, dt, -jnp.exp(p["A_log"]), p["D"], dot)
    y = y.reshape(bsz, seq, d_inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], cfg["norm_eps"])
    return dot("bse,ed->bsd", y, p["out_proj"])


def mamba_layers(layers: dict, cfg: dict, x, dot: Dot):
    def body(h, lp):
        return dot.act(h + mamba_block(lp, cfg, h, dot)), None

    x, _ = lax.scan(jax.checkpoint(body), x, layers)
    return x


def loss(params: dict, cfg: dict, tokens, dot: Dot):
    embed = params["embedding"]["embed"]
    x = mamba_layers(params["stack"]["layers"], cfg, dot.act(embed[tokens]),
                     dot)
    x = rms_norm(x, params["stack"]["ln_f"], cfg["norm_eps"])
    return cross_entropy_loss(x, embed, tokens, dot)


# ---------------------------------------------------------------- FLOPs

def mamba_layer_flops_per_token(cfg: dict) -> int:
    """Matmul and einsum FLOPs of one block's forward, per token, as the
    chunked SSD algorithm needs them (chunk L): projections, C.B within a
    chunk, the chunk's y, the chunk states and their read-out."""
    d = cfg["d_model"]
    d_inner, nh, _, n = dims(cfg)
    chunk = cfg["ssm"]["chunk_size"]
    d_proj = 2 * d_inner + 2 * n + nh
    return 2 * (d * d_proj            # in_proj
                + chunk * n           # C . B inside the chunk
                + chunk * d_inner     # intra-chunk y
                + n * d_inner         # chunk states
                + n * d_inner         # inter-chunk read-out
                + d_inner * d)        # out_proj


def logits_flops(cfg: dict, batch: int, seq: int) -> int:
    return 2 * cfg["d_model"] * cfg["vocab_size"] * batch * (seq - 1)


def flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: the forward's matmuls and
    einsums, times 3 for the backward.  Recomputation is not counted."""
    fwd = (cfg["n_layers"] * mamba_layer_flops_per_token(cfg) * batch * seq
           + logits_flops(cfg, batch, seq))
    return 3 * fwd
