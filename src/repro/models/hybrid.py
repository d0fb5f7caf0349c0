"""Zamba2 hybrid (arXiv:2411.15242): pre-norm Mamba2 layers, and one
shared attention+MLP block whose output enters the Mamba layer at every
hybrid site.

Every layer is ``x = x + mixer(rms_norm(x_in))``, with x_in = x, except
at the hybrid layers k, 2k, ... below ``n_layers`` (k =
``shared_attn_every``).  Site i, at layer (i + 1) k, runs the shared block
on the running hidden x and the embedding output e, and its output t
enters that layer's input, x_in = x + t, not the residual:

    u = rms_norm([x | e])                              2 d wide
    a = attention(rope(u Wq), rope(u Wk), u Wv) Wo     scale (head_dim / 2)^-0.5
    m = rms_norm(a)                                    no residual in the block
    g | h = m W_gate_up + (m A_i) B_i                  the site's LoRA adapter
    t = ((gelu_erf(g) * h) W_down) L_i                 the site's own linear

The shared weights are one set; the adapters A_i, B_i and linears L_i are
stacked over the sites.  The layers between two sites are one ``lax.scan``
(remat per layer); a site and its hybrid layer are one remat unit.  Its
KV caches are per site, (sites, B, S, KVH, head_dim).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_batch
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    activation,
    apply_rope,
    attention,
    dense_init,
    dtype_of,
    project_out,
    project_qkv,
    rms_norm,
    rms_norm_init,
    rope_table,
)
from repro.models.transformer import _remat


def hybrid_layer_ids(cfg: ModelConfig) -> list:
    """The layers at which a site feeds the shared block in: k, 2k, ...
    below ``n_layers``, so no site follows the last layer."""
    k = cfg.shared_attn_every
    return list(range(k, cfg.n_layers, k)) if k else []


def n_shared_sites(cfg: ModelConfig) -> int:
    return len(hybrid_layer_ids(cfg))


def segments(cfg: ModelConfig) -> list:
    """[(start, stop, site)]: the layers in runs, each beginning at a
    hybrid layer with its site's index (None for the leading run)."""
    ids = hybrid_layer_ids(cfg)
    starts, stops = [0] + ids, ids + [cfg.n_layers]
    return [(a, b, i - 1 if i else None)
            for i, (a, b) in enumerate(zip(starts, stops))]


def scan_trips(cfg: ModelConfig) -> set:
    """Trip counts of the layer scans: each run less its hybrid layer."""
    return {b - a - (s is not None) for a, b, s in segments(cfg)} - {0}


def shared_attn_scale(cfg: ModelConfig) -> float:
    """Zamba2 scales the shared attention's scores by (head_dim / 2)^-0.5:
    its heads are twice as wide as the hidden size's share."""
    return (cfg.resolved_head_dim / 2) ** -0.5


# ---------------------------------------------------------------------------
# init


def shared_init(key, cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    return {
        "ln_attn": rms_norm_init(2 * d),
        "attn": {
            "wq": dense_init(ks[0], (2 * d, h, hd), dt, fan_in=2 * d),
            "wk": dense_init(ks[1], (2 * d, kvh, hd), dt, fan_in=2 * d),
            "wv": dense_init(ks[2], (2 * d, kvh, hd), dt, fan_in=2 * d),
            "wo": dense_init(ks[3], (h, hd, d), dt, fan_in=h * hd),
        },
        "ln_ff": rms_norm_init(d),
        "mlp": {
            "w_gate_up": dense_init(ks[4], (d, 2, ff), dt, fan_in=d),
            "w2": dense_init(ks[5], (ff, d), dt, fan_in=ff),
        },
    }


def sites_init(key, cfg: ModelConfig) -> dict:
    d, ff, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    n = n_shared_sites(cfg)
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    return {
        "adapter_a": dense_init(ks[0], (n, d, r), dt, fan_in=d),
        "adapter_b": dense_init(ks[1], (n, r, 2, ff), dt, fan_in=r),
        "linear": dense_init(ks[2], (n, d, d), dt, fan_in=d),
    }


def hybrid_stack_init(key, cfg: ModelConfig) -> dict:
    k_mamba, k_shared, k_sites = jax.random.split(key, 3)
    mamba = jax.vmap(lambda k: ssm_lib.mamba_init(k, cfg))(
        jax.random.split(k_mamba, cfg.n_layers))
    mamba["in_norm"] = jnp.zeros((cfg.n_layers, cfg.d_model), jnp.float32)
    return {
        "mamba": mamba,
        "shared": shared_init(k_shared, cfg),
        "sites": sites_init(k_sites, cfg),
        "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# blocks


def _at(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _slice(tree, start: int, stop: int):
    return jax.tree.map(lambda a: a[start:stop], tree)


def shared_block(p: dict, sp: dict, cfg: ModelConfig, x, e, rope, attend):
    """The shared block with a site's adapter and linear ``sp`` on the
    hidden x and the embedding output e.  ``attend(q, k, v)`` returns the
    attention's output and what the caller keeps of k, v.  Returns (t, kept)."""
    cdt = dtype_of(cfg.compute_dtype)
    with jax.named_scope("shared_attn"):
        u = rms_norm(jnp.concatenate([x, e], axis=-1), p["ln_attn"],
                     cfg.norm_eps)
        q, k, v = project_qkv(p["attn"], cfg, u)
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        o, kept = attend(q, k, v)
        a = project_out(p["attn"], cfg, o)
    with jax.named_scope("shared_mlp"):
        m = rms_norm(a, p["ln_ff"], cfg.norm_eps)
        lora = m @ sp["adapter_a"].astype(cdt)
        gu = (jnp.einsum("bsd,dgf->bsgf", m, p["mlp"]["w_gate_up"].astype(cdt))
              + jnp.einsum("bsr,rgf->bsgf", lora, sp["adapter_b"].astype(cdt)))
        h = activation(cfg.act)(gu[..., 0, :]) * gu[..., 1, :]
        t = (h @ p["mlp"]["w2"].astype(cdt)) @ sp["linear"].astype(cdt)
    return t, kept


def _mixer_input(p: dict, cfg: ModelConfig, x, t=None):
    """The Mamba layer's pre-norm, of x plus the site's output where the
    layer is hybrid."""
    with jax.named_scope("in_proj"):
        return rms_norm(x if t is None else x + t, p["in_norm"], cfg.norm_eps)


def hybrid_forward(params, cfg: ModelConfig, e, *, collect_state: bool = False):
    """Train/prefill forward from the embedding output e (B, S, d).
    Returns (x, aux, caches), caches (when collect_state) holding the
    per-layer Mamba states and the per-site shared-attention KV."""
    rope = rope_table(jnp.arange(e.shape[1]), cfg.resolved_head_dim,
                      cfg.rope_theta)
    scale = shared_attn_scale(cfg)

    def attend(q, k, v):
        o = attention(cfg, q, k, v, causal=True, scale=scale)
        return o, ((k, v) if collect_state else None)

    def layer(h, p, t=None):
        x_in = _mixer_input(p, cfg, h, t)
        if collect_state:
            y, states = ssm_lib.mamba_prefill(p, cfg, x_in)
            return shard_batch(h + y), states
        return shard_batch(h + ssm_lib.mamba_forward(p, cfg, x_in)), None

    def site_layer(h, e, shared, sp, p):
        t, kv = shared_block(shared, sp, cfg, h, e, rope, attend)
        h, states = layer(h, p, t)
        return h, (states, kv)

    x = e
    conv_states, ssm_states, shared_kv = [], [], []
    for start, stop, site in segments(cfg):
        if site is not None:
            x, (states, kv) = _remat(cfg, site_layer)(
                x, e, params["shared"], _at(params["sites"], site),
                _at(params["mamba"], start))
            if collect_state:
                conv_states.append(states[0][None])
                ssm_states.append(states[1][None])
                shared_kv.append(kv)
            start += 1
        if stop > start:
            x, states = lax.scan(_remat(cfg, layer), x,
                                 _slice(params["mamba"], start, stop))
            if collect_state:
                conv_states.append(states[0])
                ssm_states.append(states[1])

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if not collect_state:
        return x, {}, None
    cache = {
        "conv": jnp.concatenate(conv_states, axis=0),
        "ssm": jnp.concatenate(ssm_states, axis=0),
        "shared_k": jnp.stack([kv[0] for kv in shared_kv]),
        "shared_v": jnp.stack([kv[1] for kv in shared_kv]),
    }
    return x, {}, cache


def hybrid_decode(params, cfg: ModelConfig, e, cache, pos):
    """e: the embedding output (B, 1, d).  cache: conv (L, B, W-1, Ch),
    ssm (L, B, nh, N, P), shared_k/v (sites, B, S, KVH, head_dim);
    pos: (B,) write positions."""
    rope = rope_table(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta)
    scale = shared_attn_scale(cfg)
    bidx = jnp.arange(e.shape[0])

    def step(h, p, conv, state, t=None):
        x_in = _mixer_input(p, cfg, h, t)
        y, new_conv, new_state = ssm_lib.mamba_decode_step(p, cfg, x_in, conv,
                                                           state)
        return shard_batch(h + y), new_conv, new_state

    def body(h, inputs):
        h, new_conv, new_state = step(h, *inputs)
        return h, (new_conv, new_state)

    def attend_at(site):
        def attend(q, k, v):
            ck = cache["shared_k"][site].at[bidx, pos].set(
                k[:, 0].astype(cache["shared_k"].dtype))
            cv = cache["shared_v"][site].at[bidx, pos].set(
                v[:, 0].astype(cache["shared_v"].dtype))
            o = attention(cfg, q, ck, cv, causal=False, q_offset=pos,
                          k_valid=pos + 1, scale=scale)
            return o, (ck, cv)
        return attend

    x = e
    new_conv, new_ssm, new_k, new_v = [], [], [], []
    for start, stop, site in segments(cfg):
        if site is not None:
            t, (ck, cv) = shared_block(params["shared"],
                                       _at(params["sites"], site), cfg,
                                       x, e, rope, attend_at(site))
            x, conv, state = step(x, _at(params["mamba"], start),
                                  cache["conv"][start], cache["ssm"][start], t)
            new_conv.append(conv[None])
            new_ssm.append(state[None])
            new_k.append(ck)
            new_v.append(cv)
            start += 1
        if stop > start:
            x, (conv, state) = lax.scan(
                body, x, (_slice(params["mamba"], start, stop),
                          cache["conv"][start:stop], cache["ssm"][start:stop]))
            new_conv.append(conv)
            new_ssm.append(state)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    new_cache = {
        "conv": jnp.concatenate(new_conv, axis=0),
        "ssm": jnp.concatenate(new_ssm, axis=0),
        "shared_k": jnp.stack(new_k),
        "shared_v": jnp.stack(new_v),
    }
    return x, new_cache, {}
