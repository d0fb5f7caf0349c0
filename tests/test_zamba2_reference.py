"""The hybrid model (``models/hybrid.py``) against the plain float32
reference of Zamba2 (``bench/reference/zamba2.py``), on the CPU at a tiny
size, on the reference's seeded weights: the loss and every gradient leaf
of ``loss_fn``, with the benchmark configuration's block at two sites."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.cell import model_config  # noqa: E402
from reference import zamba2 as ref  # noqa: E402
from reference.common import Dot  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import hybrid, loss_fn  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

# Each tolerance is a relative gap, |program - reference| / |reference|,
# of the loss or of a gradient leaf's norm of differences.  Readings at
# this size: float32 compute 1.7e-7 (loss) and 1.3e-6 (worst leaf): the
# same mathematics, summed in another order, and the SSD chunked against
# its dual form.  bfloat16 compute, as the configuration runs: 1.1e-5 and
# 0.031 (median leaf 0.022), the rounding of every matmul operand and of
# the residual stream.  The float8 control (the reference a precision
# step lower): 8.9e-4 and 0.27, so it fails both bfloat16 tolerances.
TOLERANCE = {
    "float32": {"loss": 1e-5, "leaf": 1e-4},
    "bfloat16": {"loss": 1e-4, "leaf": 0.08},
}
BATCH, SEQ = 2, 64


def tiny_model(**kw) -> dict:
    with open(os.path.join(BENCH, "configs", "zamba2-1.2b.json")) as f:
        model = json.load(f)["model"]
    # five layers, sites at 2 and 4 (the last feeds the last layer)
    model.update(n_layers=5, d_model=64, n_heads=4, n_kv_heads=4,
                 head_dim=32, d_ff=128, vocab_size=256, adapter_rank=8,
                 shared_attn_every=2, vocab_chunk=0)
    model["ssm"].update(state_dim=16, head_dim=16, chunk_size=32)
    model.update(kw)
    return model


@pytest.fixture(scope="module")
def weights():
    params = ref.init(tiny_model(), jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (BATCH, SEQ), 0, 256)
    return params, tokens


def reference_grads(params, tokens, precision: str):
    model = tiny_model()
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, model, tokens, Dot(precision))))(params)


@pytest.fixture(scope="module")
def reference(weights):
    return {p: reference_grads(*weights, p) for p in ("f32", "fp8")}


def program_grads(params, tokens, compute: str):
    cfg = model_config(tiny_model(compute_dtype=compute))
    return jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, cfg, {"tokens": tokens})[0]))(params)


def gaps(got, want):
    """(loss gap, worst leaf gap, leaf name)."""
    (loss, grads), (ref_loss, ref_grads) = got, want
    flat, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    leaf = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads))]
    worst = int(np.argmax(leaf))
    name = "/".join(str(getattr(k, "key", k)) for k in flat[worst][0])
    return abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)), \
        leaf[worst], name


def within(g, tol) -> bool:
    return g[0] <= tol["loss"] and g[1] <= tol["leaf"]


@pytest.mark.parametrize("compute", sorted(TOLERANCE))
def test_loss_and_every_gradient_leaf_match_the_reference(weights, reference,
                                                          compute):
    g = gaps(program_grads(*weights, compute), reference["f32"])
    assert within(g, TOLERANCE[compute]), g


def test_the_lower_precision_control_fails_a_tolerance(reference):
    g = gaps(reference["fp8"], reference["f32"])
    assert not within(g, TOLERANCE["bfloat16"]), g


def test_attention_scale_is_half_head_dim(weights, reference, monkeypatch):
    assert hybrid.shared_attn_scale(get_config("zamba2-1.2b")) == 0.125
    assert hybrid.shared_attn_scale(model_config(tiny_model())) == 0.25
    monkeypatch.setattr(hybrid, "shared_attn_scale",
                        lambda cfg: cfg.resolved_head_dim ** -0.5)
    g = gaps(program_grads(*weights, "float32"), reference["f32"])
    assert not within(g, TOLERANCE["float32"]), g


def test_each_site_has_its_own_adapter_and_linear_gradient(weights):
    _, grads = program_grads(*weights, "float32")
    for name, g in grads["stack"]["sites"].items():
        assert g.shape[0] == 2, name
        norms = [float(jnp.linalg.norm(g[i])) for i in range(2)]
        assert min(norms) > 0, name
        assert float(jnp.linalg.norm(g[0] - g[1])) > 1e-3 * max(norms), name


@pytest.mark.parametrize("layers,sites,runs,trips", [
    (18, 2, [(0, 6, None), (6, 12, 0), (12, 18, 1)], {6, 5}),
    (38, 6, [(0, 6, None)] + [(6 * i, 6 * i + 6, i - 1) for i in range(1, 6)]
     + [(36, 38, 5)], {6, 5, 1}),
])
def test_sites_follow_every_sixth_layer_below_the_last(layers, sites, runs,
                                                       trips):
    cfg = get_config("zamba2-1.2b").replace(n_layers=layers)
    assert hybrid.n_shared_sites(cfg) == sites
    assert hybrid.segments(cfg) == runs
    assert hybrid.scan_trips(cfg) == trips


def test_the_trainer_gauges_the_compiled_steps_shared_sites(tmp_path):
    cfg = get_config("zamba2-1.2b", reduced=True)
    rng = np.random.default_rng(0)
    batches = (rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
               for _ in range(8))
    trainer = Trainer(cfg, TrainerConfig(
        steps=1, checkpoint_every=100, log_every=100,
        checkpoint_dir=str(tmp_path / "ck")), batches)
    gauges = trainer.run()["telemetry"]["gauges"]
    assert gauges["model.shared_sites"] == hybrid.n_shared_sites(cfg) == 2
    assert gauges["model.ssd_kernel"] == 0.0           # the CPU's XLA scan
