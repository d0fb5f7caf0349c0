"""Model facade: init / forward / loss / prefill / decode for every
assigned architecture family.

Batch dict keys:
  tokens        (B, S) int32            — always present
  loss_mask     (B, S) float32          — optional (defaults to ones)
  vision_embeds (B, Tv, d) bf16         — vlm stub frontend output
  audio_frames  (B, S_enc, d) bf16      — encdec stub frontend output
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_batch
from repro.models import encdec as encdec_lib
from repro.models import hybrid as hybrid_lib
from repro.models import ssm as ssm_lib
from repro.models import transformer as tf_lib
from repro.models.layers import (
    dtype_of,
    embed_tokens,
    embedding_init,
    rms_norm,
    sinusoidal_positions,
    unembed_matrix,
)
from repro.models.loss import cross_entropy, masked_mean
from repro.models.ssm import dims as ssm_dims


# ---------------------------------------------------------------------------
# init


def init_params(cfg: ModelConfig, key) -> dict:
    k_embed, k_stack = jax.random.split(key)
    p = {"embedding": embedding_init(k_embed, cfg)}
    if cfg.family in ("dense", "moe", "vlm"):
        p["stack"] = tf_lib.dense_stack_init(k_stack, cfg)
    elif cfg.family == "ssm":
        keys = jax.random.split(k_stack, cfg.n_layers)
        p["stack"] = {
            "layers": jax.vmap(lambda k: ssm_lib.mamba_init(k, cfg))(keys),
            "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    elif cfg.family == "hybrid":
        p["stack"] = hybrid_lib.hybrid_stack_init(k_stack, cfg)
    elif cfg.family == "encdec":
        p["stack"] = encdec_lib.encdec_stack_init(k_stack, cfg)
    else:
        raise ValueError(cfg.family)
    return p


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def shared_sites(cfg: ModelConfig) -> int:
    """Applications of the hybrid family's shared block in one forward."""
    return hybrid_lib.n_shared_sites(cfg) if cfg.family == "hybrid" else 0


# ---------------------------------------------------------------------------
# forward (train / prefill)


def _ssm_forward(params, cfg: ModelConfig, x, collect_state: bool):
    def body(h, p):
        if collect_state:
            y, st = ssm_lib.mamba_prefill(p, cfg, h)
            return shard_batch(h + y), st
        return shard_batch(h + ssm_lib.mamba_forward(p, cfg, h)), None

    G = cfg.remat_group
    if G > 1 and cfg.n_layers % G == 0 and not collect_state:
        per = cfg.n_layers // G
        grouped = jax.tree.map(
            lambda a: a.reshape((G, per) + a.shape[1:]), params["layers"])

        def group_body(h, gp):
            h, _ = lax.scan(tf_lib._remat(cfg, body), h, gp)
            return h, None

        x, states = lax.scan(tf_lib._remat(cfg, group_body), x, grouped)
    else:
        x, states = lax.scan(tf_lib._remat(cfg, body), x, params["layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    cache = None
    if collect_state:
        cache = {"conv": states[0], "ssm": states[1]}
    return x, {}, cache


def forward(params, cfg: ModelConfig, batch: dict, *,
            collect_kv: bool = False):
    """Returns (hidden (B,S,d), aux dict, caches-or-None)."""
    tokens = batch["tokens"]
    x = shard_batch(embed_tokens(params["embedding"], cfg, tokens))
    stack = params["stack"]
    if cfg.family in ("dense", "moe"):
        return tf_lib.dense_forward(stack, cfg, x, collect_kv=collect_kv)
    if cfg.family == "vlm":
        vision = batch["vision_embeds"].astype(x.dtype)
        return tf_lib.vlm_forward(stack, cfg, x, vision, collect_kv=collect_kv)
    if cfg.family == "ssm":
        return _ssm_forward(stack, cfg, x, collect_kv)
    if cfg.family == "hybrid":
        return hybrid_lib.hybrid_forward(stack, cfg, x,
                                         collect_state=collect_kv)
    if cfg.family == "encdec":
        enc = encdec_lib.encode(stack, cfg, batch["audio_frames"])
        S = tokens.shape[1]
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
        h, kvs = encdec_lib.decode_train(stack, cfg, x, enc,
                                         collect_kv=collect_kv)
        cache = None
        if collect_kv:
            cache = {"k": kvs[0], "v": kvs[1], "xk": kvs[2], "xv": kvs[3]}
        return h, {}, cache
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# loss


def loss_fn(params, cfg: ModelConfig, batch: dict):
    tokens = batch["tokens"]
    hidden, aux, _ = forward(params, cfg, batch)
    labels = tokens[:, 1:]
    with jax.named_scope("loss"):
        unembed = unembed_matrix(params["embedding"], cfg)
        per_token = cross_entropy(hidden[:, :-1, :], unembed, labels, cfg)
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(per_token) if mask is None else mask[:, 1:]
    ce = masked_mean(per_token, mask)
    loss = ce
    metrics = {"ce_loss": ce}
    for k, v in (aux or {}).items():
        metrics[k] = v
        if k.endswith("_loss"):
            loss = loss + v
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Zero-initialized decode cache (the dry-run decode cells feed this
    shape as a ShapeDtypeStruct input)."""
    cdt = dtype_of(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    L, KVH = cfg.n_layers, cfg.n_kv_heads
    fam = cfg.family
    if fam in ("dense", "moe"):
        return {
            "k": jnp.zeros((L, batch, max_len, KVH, hd), cdt),
            "v": jnp.zeros((L, batch, max_len, KVH, hd), cdt),
        }
    if fam == "vlm":
        group = cfg.cross_attn_every - 1
        G = cfg.n_layers // cfg.cross_attn_every
        return {
            "self_k": jnp.zeros((G, group, batch, max_len, KVH, hd), cdt),
            "self_v": jnp.zeros((G, group, batch, max_len, KVH, hd), cdt),
            "cross_k": jnp.zeros((G, batch, cfg.vision_tokens, KVH, hd), cdt),
            "cross_v": jnp.zeros((G, batch, cfg.vision_tokens, KVH, hd), cdt),
        }
    if fam == "ssm":
        d_inner, nh, P, N = ssm_dims(cfg)
        ch = d_inner + 2 * N
        W = cfg.ssm.conv_width
        return {
            "conv": jnp.zeros((L, batch, W - 1, ch), cdt),
            "ssm": jnp.zeros((L, batch, nh, N, P), jnp.float32),
        }
    if fam == "hybrid":
        d_inner, nh, P, N = ssm_dims(cfg)
        ch = d_inner + 2 * N
        W = cfg.ssm.conv_width
        sites = hybrid_lib.n_shared_sites(cfg)
        return {
            "conv": jnp.zeros((L, batch, W - 1, ch), cdt),
            "ssm": jnp.zeros((L, batch, nh, N, P), jnp.float32),
            "shared_k": jnp.zeros((sites, batch, max_len, KVH, hd), cdt),
            "shared_v": jnp.zeros((sites, batch, max_len, KVH, hd), cdt),
        }
    if fam == "encdec":
        H = cfg.n_heads
        return {
            "k": jnp.zeros((L, batch, max_len, H, hd), cdt),
            "v": jnp.zeros((L, batch, max_len, H, hd), cdt),
            "xk": jnp.zeros((L, batch, cfg.encoder_seq, H, hd), cdt),
            "xv": jnp.zeros((L, batch, cfg.encoder_seq, H, hd), cdt),
        }
    raise ValueError(fam)


def _pad_seq(a: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def prefill(params, cfg: ModelConfig, batch: dict,
            pad_to: Optional[int] = None):
    """Full-sequence forward building decode caches.  Returns
    (cache, last_logits (B, V), next_pos (B,))."""
    hidden, _, cache = forward(params, cfg, batch, collect_kv=True)
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.family in ("dense", "moe"):
        kvs = cache
        cache = {"k": kvs[0], "v": kvs[1]}
    elif cfg.family == "vlm":
        kvs, ckv = cache
        cache = {"self_k": kvs[0], "self_v": kvs[1],
                 "cross_k": ckv[0], "cross_v": ckv[1]}
    if pad_to:
        axis_by_key = {"k": 2, "v": 2, "self_k": 3, "self_v": 3,
                       "shared_k": 2, "shared_v": 2}
        cache = {k: (_pad_seq(v, axis_by_key[k], pad_to)
                     if k in axis_by_key else v)
                 for k, v in cache.items()}
    unembed = unembed_matrix(params["embedding"], cfg)
    logits = jnp.einsum("bd,dv->bv", hidden[:, -1, :], unembed,
                        preferred_element_type=jnp.float32)
    pos = jnp.full((B,), S, jnp.int32)
    return cache, logits, pos


# ---------------------------------------------------------------------------
# decode


def decode_step(params, cfg: ModelConfig, cache: dict, tokens: jnp.ndarray,
                pos: jnp.ndarray):
    """tokens: (B, 1) int32, pos: (B,) int32 write positions.

    Returns (logits (B, V) f32, new_cache)."""
    x = embed_tokens(params["embedding"], cfg, tokens)
    stack = params["stack"]
    fam = cfg.family
    if fam in ("dense", "moe"):
        h, cache, _ = tf_lib.dense_decode(stack, cfg, x, cache, pos)
    elif fam == "vlm":
        h, cache, _ = tf_lib.vlm_decode(stack, cfg, x, cache, pos)
    elif fam == "ssm":
        def body(carry, inputs):
            hh = carry
            p, conv, hstate = inputs
            y, nc, nh_ = ssm_lib.mamba_decode_step(p, cfg, hh, conv, hstate)
            return hh + y, (nc, nh_)
        h, (conv, sstate) = lax.scan(
            body, x, (stack["layers"], cache["conv"], cache["ssm"]))
        h = rms_norm(h, stack["ln_f"], cfg.norm_eps)
        cache = {"conv": conv, "ssm": sstate}
    elif fam == "hybrid":
        h, cache, _ = hybrid_lib.hybrid_decode(stack, cfg, x, cache, pos)
    elif fam == "encdec":
        x = x + jnp.take(
            sinusoidal_positions(cache["k"].shape[2], cfg.d_model),
            pos, axis=0)[:, None, :].astype(x.dtype)
        h, cache = encdec_lib.decode_step(stack, cfg, x, cache, pos)
    else:
        raise ValueError(fam)
    unembed = unembed_matrix(params["embedding"], cfg)
    logits = jnp.einsum("bd,dv->bv", h[:, -1, :], unembed,
                        preferred_element_type=jnp.float32)
    return logits, cache
