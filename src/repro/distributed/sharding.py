"""Sharding rules: map parameter / activation / cache pytrees onto the
production mesh.

Axes
----
``("data", "model")`` single-pod, ``("pod", "data", "model")`` multi-pod.

* batch & FSDP axis = ``("pod", "data")`` (or ``("data",)``) — activations
  shard batch over it; parameters and optimizer state shard their largest
  replicable dim over it (ZeRO-3 style).
* tensor-parallel axis = ``"model"`` — attention heads, ffn hidden, vocab.
* expert parallelism (``moe.expert_sharding == "ep"``) moves the expert dim
  onto "model" instead of the ffn dim.
* sequence parallelism: long-context decode caches shard the sequence dim
  over "model" (and "data" when batch==1) when the KV-head dim is not
  divisible by the model-axis size.

Every rule is divisibility-guarded: a dim that does not divide evenly over
its target axis is left unsharded rather than producing an invalid spec.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fit(mesh: Mesh, dim: int, axes):
    """Return axes if dim divides evenly over them, else None."""
    if axes is None or dim <= 0:
        return None
    n = axis_size(mesh, axes)
    return axes if (n > 1 and dim % n == 0) else None


# ---------------------------------------------------------------------------
# parameter specs


def _trailing_spec(name: str, shape, cfg: ModelConfig, mesh: Mesh,
                   in_moe: bool) -> list:
    """PartitionSpec entries for the semantic trailing dims of a leaf."""
    fsdp = batch_axes(mesh)
    tp = "model"
    ep = cfg.moe.expert_sharding == "ep"

    def f(dim):
        return _fit(mesh, dim, fsdp)

    def t(dim):
        return _fit(mesh, dim, tp)

    if name in ("wq", "wk", "wv"):              # (d, h, hd)
        d, h, hd = shape[-3:]
        return [f(d), t(h), None]
    if name in ("bq", "bk", "bv"):              # (h, hd)
        h, hd = shape[-2:]
        return [t(h), None]
    if name == "wo":                            # (h, hd, d)
        h, hd, d = shape[-3:]
        return [t(h), None, f(d)]
    if name in ("w1", "w3"):
        if in_moe:                              # (E, d, ff)
            E, d, ff = shape[-3:]
            if ep:
                return [t(E), f(d), None]
            return [None, f(d), t(ff)]
        d, ff = shape[-2:]                      # (d, ff)
        return [f(d), t(ff)]
    if name == "w2":
        if in_moe:                              # (E, ff, d)
            E, ff, d = shape[-3:]
            if ep:
                return [t(E), None, f(d)]
            return [None, t(ff), f(d)]
        ff, d = shape[-2:]                      # (ff, d)
        return [t(ff), f(d)]
    if name == "w_gate_up":                     # (d, 2, ff) — zamba2 shared
        d, _, ff = shape[-3:]
        return [f(d), None, t(ff)]
    if name == "adapter_a":                     # (sites, d, r)
        d, r = shape[-2:]
        return [f(d), None]
    if name == "adapter_b":                     # (sites, r, 2, ff)
        r, _, ff = shape[-3:]
        return [None, None, t(ff)]
    if name == "linear":                        # (sites, d, d)
        d_in, d_out = shape[-2:]
        return [f(d_in), None]
    if name == "router":                        # (d, E)
        d, E = shape[-2:]
        return [f(d), None]
    if name == "embed":                         # (V, d)
        V, d = shape[-2:]
        return [t(V), f(d)]
    if name == "unembed":                       # (d, V)
        d, V = shape[-2:]
        return [f(d), t(V)]
    if name == "in_proj":                       # (d, d_proj) — ssm packed
        d, dp = shape[-2:]
        return [f(d), None]
    if name == "out_proj":                      # (d_inner, d)
        di, d = shape[-2:]
        return [None, f(d)]
    # norms, scalars, conv weights, A_log/D/dt_bias, gates: replicated
    return []


def param_specs(cfg: ModelConfig, params_shape,
                mesh: Mesh):
    """Tree of PartitionSpec matching a params (shape) tree."""

    def spec_for(path, leaf) -> P:
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        name = names[-1]
        in_moe = "moe" in names
        shape = leaf.shape
        trailing = _trailing_spec(name, shape, cfg, mesh, in_moe)
        pad = len(shape) - len(trailing)
        return P(*([None] * pad + trailing))

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


# ---------------------------------------------------------------------------
# batch / activation specs


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> dict:
    """Input sharding for a train/prefill batch dict."""
    ba = batch_axes(mesh)
    b = _fit(mesh, shape.global_batch, ba)
    specs = {"tokens": P(b, None)}
    if cfg.family == "vlm":
        specs["vision_embeds"] = P(b, None, None)
    if cfg.family == "encdec":
        specs["audio_frames"] = P(b, None, None)
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> dict:
    """Decode-cache sharding.  Shards batch over the data axes; KV heads
    over "model" when divisible, otherwise the cache sequence dim (SP)."""
    ba = batch_axes(mesh)
    B = shape.global_batch
    b = _fit(mesh, B, ba)
    tp_kv = _fit(mesh, cfg.n_kv_heads, "model")
    # sequence parallelism over whatever axes are left idle: the "model"
    # axis when KV heads do not divide over it, plus the batch axes when
    # the batch itself cannot shard (long_500k has global_batch == 1).
    seq_axes = []
    if b is None:
        seq_axes.extend(ba)
    if tp_kv is None:
        seq_axes.append("model")
    s = _fit(mesh, shape.seq_len, tuple(seq_axes)) if seq_axes else None

    fam = cfg.family
    if fam in ("dense", "moe"):
        kv = P(None, b, s, tp_kv, None)
        return {"k": kv, "v": kv}
    if fam == "vlm":
        kv = P(None, None, b, s, tp_kv, None)
        xkv = P(None, b, None, tp_kv, None)
        return {"self_k": kv, "self_v": kv, "cross_k": xkv, "cross_v": xkv}
    if fam == "ssm":
        from repro.models.ssm import dims as ssm_dims
        _, nh, _, _ = ssm_dims(cfg)
        return {
            "conv": P(None, b, None, _fit(mesh, cfg.ssm.expand * cfg.d_model
                                          + 2 * cfg.ssm.state_dim, "model")),
            "ssm": P(None, b, _fit(mesh, nh, "model"), None, None),
        }
    if fam == "hybrid":
        from repro.models.ssm import dims as ssm_dims
        _, nh, _, _ = ssm_dims(cfg)
        return {
            "conv": P(None, b, None, _fit(mesh, cfg.ssm.expand * cfg.d_model
                                          + 2 * cfg.ssm.state_dim, "model")),
            "ssm": P(None, b, _fit(mesh, nh, "model"), None, None),
            "shared_k": P(None, b, s, tp_kv, None),
            "shared_v": P(None, b, s, tp_kv, None),
        }
    if fam == "encdec":
        tp_h = _fit(mesh, cfg.n_heads, "model")
        s2 = _fit(mesh, shape.seq_len, seq_axes if tp_h is None else None)
        kv = P(None, b, s2, tp_h, None)
        xkv = P(None, b, None, tp_h, None)
        return {"k": kv, "v": kv, "xk": xkv, "xv": xkv}
    raise ValueError(fam)


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation sharding policy
#
# XLA's sharding propagation resolves the FSDP conflict (params sharded over
# "data" x activations batched over "data") by whichever side looks cheaper
# locally — which can silently replicate full-batch activations.  Model code
# therefore pins activation batch dims through `shard_batch`, enabled by the
# launcher via `set_activation_axes` (smoke tests leave it unset: no-op).

_ACTIVATION_AXES: list = [None]
_TP_AXIS: list = [None]
_AXIS_SIZES: dict = {}


def set_activation_axes(batch_axes_, tp_axis: Optional[str] = "model",
                        mesh: Optional[Mesh] = None):
    """batch_axes_: tuple like ("data",) or ("pod","data"), or None to clear."""
    _ACTIVATION_AXES[0] = batch_axes_
    _TP_AXIS[0] = tp_axis
    _AXIS_SIZES.clear()
    if mesh is not None:
        _AXIS_SIZES.update({a: int(s) for a, s in mesh.shape.items()})


def activation_axes():
    return _ACTIVATION_AXES[0]


def tp_axis():
    return _TP_AXIS[0]


def _divisible(dim: int, axes) -> bool:
    if not _AXIS_SIZES:
        return True
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= _AXIS_SIZES.get(a, 1)
    return dim % n == 0


def shard_batch(x, spec_rest: tuple = ()):
    """Constrain x's leading dim to the batch axes; trailing dims per
    spec_rest (padded with None).  No-op when no policy is active or the
    leading dim does not divide over the batch axes (e.g. batch == 1)."""
    axes = _ACTIVATION_AXES[0]
    if axes is None or x.ndim == 0 or not _divisible(x.shape[0], axes):
        return x
    rest = list(spec_rest) + [None] * (x.ndim - 1 - len(spec_rest))
    return jax.lax.with_sharding_constraint(x, P(axes, *rest))


def shard_spec(x, spec: P):
    """Constrain to an explicit spec when a policy is active."""
    if _ACTIVATION_AXES[0] is None:
        return x
    for dim, entry in zip(x.shape, spec):
        if entry is not None and not _divisible(dim, entry):
            return x
    return jax.lax.with_sharding_constraint(x, spec)
