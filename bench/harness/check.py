"""The comparison that decides ``correct``.

Training (every cell): the Trainer's own step, driven from the seed
through its first three steps in set-up, against the plain reference
(``bench/reference``) trained from the same weights on the same batches.
A configuration's ``limits`` name the numbers it compares:

  loss_gap         worst of the three steps' |loss - ref| / ref
  grad_norm_gap    worst of the three steps' pre-clip global gradient
                   norm, |g - ref| / ref
  grad_diff_gap    the first step's clipped gradient, read back from the
                   optimizer's first moment (m / (1 - b1)): the median leaf
                   of |g_leaf - ref_leaf| / max(|ref_leaf|, median leaf).
                   (The worst leaf of the gap of norms swings from seed to
                   seed with one leaf, D, whose gradient is a near-cancelling
                   sum, and reads alike for bfloat16 and the float8 control;
                   see PERF.md.)
  leaf_change_gap  the parameters' change after three steps, by the same
                   rule, leaving out leaves whose reference gradient is
                   under a thousandth of the median leaf's (they move by
                   round-off alone)

Exact (limit 0): the batches the window consumed against the reference
reader's; the profiler's POSIX and STDIO record against the reads the
input layer had to make; no checkpoint byte recorded that was not
written; every checkpoint the window saved, leaf by leaf, against the
device state it was saved from.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import Dot, adamw_step

EXACT = 0.0
REF_STEPS = 3
TINY_GRAD = 1e-3          # leaves under this share of the median leaf


@dataclass
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def first_moments(opt_state):
    """The optimizer's m, in the parameters' tree."""
    return jax.tree.map(lambda s: s["m"], opt_state["leaves"],
                        is_leaf=lambda x: isinstance(x, dict) and "m" in x)


def reference_readings(ref, cfg: dict, opt: dict, key, batches: List,
                       precision: str = "f32") -> Dict[str, np.ndarray]:
    """Trains the reference for ``len(batches)`` steps from the
    benchmark's weights.  Returns its losses, pre-clip gradient norms,
    the first step's clipped gradient (host leaves) and its per-leaf
    norms, and the per-leaf norms of the change after the last step."""
    dot = Dot(precision)
    init = jax.jit(lambda k: ref.init(cfg, k))

    def grad_rows(params, acc, loss_acc, tokens, weight):
        """Adds ``weight`` times the loss and gradient of these rows: the
        batch is taken a row at a time, so that the reference fits."""
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(
                lambda p: ref.loss(p, cfg, tokens, dot))(params)
        acc = jax.tree.map(lambda a, g: a + weight * g, acc, grads)
        return acc, loss_acc + weight * loss

    def update(params, m, v, grads, t):
        params, m, v, gnorm, g = adamw_step(opt, params, grads, m, v, t)
        return params, m, v, gnorm, g

    grad_rows = jax.jit(grad_rows, donate_argnums=(1,))
    update = jax.jit(update, donate_argnums=(0, 1, 2))
    params = init(key)
    # Adam's moments wait on the host while the gradient is computed
    moments = jax.device_get(jax.tree.map(jnp.zeros_like, (params, params)))
    losses, gnorms, grad = [], [], None
    for t, tokens in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, params)
        loss = jnp.zeros((), jnp.float32)
        weight = jnp.float32(1.0 / len(tokens))
        for row in range(len(tokens)):
            acc, loss = grad_rows(params, acc, loss,
                                  jnp.asarray(tokens[row:row + 1]), weight)
        m, v = jax.device_put(moments)
        params, m, v, gnorm, g = update(params, m, v, acc, jnp.int32(t))
        moments = jax.device_get((m, v))
        del m, v
        losses.append(float(loss))
        gnorms.append(float(gnorm))
        if grad is None:
            grad = [np.asarray(x) for x in jax.device_get(jax.tree.leaves(g))]
        del g
    del moments
    change = change_norms(ref, cfg)(params, key)
    return {"loss": np.asarray(losses), "grad_norm": np.asarray(gnorms),
            "grad": grad, "leaf_grad": host_leaf_norms(grad),
            "leaf_change": np.asarray(change)}


def host_leaf_norms(leaves) -> np.ndarray:
    return np.asarray([np.sqrt(np.sum(np.square(x, dtype=np.float64)))
                       for x in leaves])


def diff_norms(a, b) -> np.ndarray:
    return np.asarray([np.sqrt(np.sum(np.square(
        np.subtract(x, y, dtype=np.float64)))) for x, y in zip(a, b)])


def change_norms(ref, cfg: dict):
    """jit: per-leaf norms of ``params - init(key)``."""
    return jax.jit(lambda p, k: leaf_norms(
        jax.tree.map(jnp.subtract, p, ref.init(cfg, k))))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _median_leaf(diff, refv) -> float:
    refv = np.asarray(refv, np.float64)
    return float(np.median(diff / np.maximum(refv, np.median(refv))))


def _worst_leaf(prog, refv, keep=None) -> float:
    prog, refv = np.asarray(prog, np.float64), np.asarray(refv, np.float64)
    if keep is not None:
        prog, refv = prog[keep], refv[keep]
    floor = np.median(refv)
    return float(np.max(np.abs(prog - refv) / np.maximum(refv, floor)))


def training_numbers(prog: Dict[str, np.ndarray], refr: Dict[str, np.ndarray],
                     limits: Dict[str, float]) -> List[Number]:
    """``prog`` and ``refr`` hold the same keys as ``reference_readings``
    returns.  Only the numbers that ``limits`` names are compared: a
    number that neither the control nor a fault separates from sound runs
    has no limit."""
    g = refr["leaf_grad"]
    keep = g >= TINY_GRAD * np.median(g)
    values = {
        "loss_gap": _rel(prog["loss"], refr["loss"]),
        "grad_norm_gap": _rel(prog["grad_norm"], refr["grad_norm"]),
        "grad_diff_gap": _median_leaf(diff_norms(prog["grad"], refr["grad"]),
                                      g),
        "leaf_change_gap": _worst_leaf(prog["leaf_change"],
                                       refr["leaf_change"], keep),
    }
    return [Number(name, value, limits[name])
            for name, value in values.items() if name in limits]


def leaf_report(ref, cfg: dict, prog, refr) -> List[str]:
    """Per-leaf readings behind the two leaf gaps, for the log."""
    shapes = jax.eval_shape(lambda k: ref.init(cfg, k), jax.random.PRNGKey(0))
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    diff = diff_norms(prog["grad"], refr["grad"])
    return [f"leaf {n} grad {p!r} ref {r!r} diff {d!r} change {pc!r} "
            f"ref {rc!r}"
            for n, p, r, d, pc, rc in zip(names, prog["leaf_grad"],
                                          refr["leaf_grad"], diff,
                                          prog["leaf_change"],
                                          refr["leaf_change"])]


def count(name: str, value, limit: float = EXACT) -> Number:
    return Number(name, float(value), limit)


def profiler_numbers(recorded: Dict[str, int], expected, ckpt_bytes: int,
                     missed_limit: Optional[float]) -> List[Number]:
    """``recorded``: ``traffic.shard_counters``; ``expected``: the
    ``traffic.BatchIO`` of the profiled calls; ``ckpt_bytes``: bytes the
    window's checkpoint files hold."""
    out = [
        count("posix_record_gap",
              abs(recorded["posix_opens"] - expected.docs)
              + abs(recorded["posix_reads"] - 2 * expected.docs)
              + abs(recorded["posix_bytes_read"] - expected.doc_bytes)),
        count("stdio_read_gap",
              abs(recorded["stdio_reads"] - expected.index_reads)
              + abs(recorded["stdio_bytes_read"] - expected.index_bytes)),
        count("stdio_write_excess_bytes",
              max(0, recorded["stdio_bytes_written"] - ckpt_bytes)),
    ]
    if ckpt_bytes and missed_limit is not None:
        missed = max(0, ckpt_bytes - recorded["stdio_bytes_written"])
        out.append(Number("stdio_write_missed_share", missed / ckpt_bytes,
                          missed_limit))
    return out


def checksums(tree) -> List:
    """Per-leaf wrapping uint32 sums of the leaves' bits (jit it)."""
    out = []
    for x in jax.tree.leaves(tree):
        if x.dtype.itemsize != 4:
            raise TypeError(f"checksum needs 4-byte leaves, got {x.dtype}")
        out.append(jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32),
                           dtype=jnp.uint32))
    return out


def host_checksum(arr: np.ndarray) -> int:
    arr = np.ascontiguousarray(arr)
    return int(arr.view(np.uint32).sum(dtype=np.uint64) % (1 << 32))


def format_numbers(numbers: List[Number]) -> List[str]:
    return [f"check {n.name} {n.value!r} limit {n.limit!r} "
            f"{'ok' if n.ok else 'FAIL'}" for n in numbers]
