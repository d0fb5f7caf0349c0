"""The configurations' FLOP counts against XLA's cost analysis of the
program's forward, at a reduced size where every loop runs once (one
layer, one SSD chunk, no vocabulary chunks), so that XLA counts each
operation once.  XLA also counts the elementwise work, which the model
count leaves out, so the model count sits a little below it."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from harness import cell as cell_lib, spec

SMALL = {
    "mamba2-370m": dict(n_layers=1, d_model=256, vocab_size=1024,
                        vocab_chunk=0, remat_group=0, remat="none"),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_flop_count_matches_xla_cost_analysis(name):
    from repro.models import init_params, loss_fn
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        config = json.load(f)
    model = config["model"]
    model.update(SMALL[name])
    model["ssm"]["chunk_size"] = seq = 128
    batch = 2
    cfg = cell_lib.model_config(model)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    compiled = jax.jit(lambda p, t: loss_fn(p, cfg, {"tokens": t})[0]).lower(
        params, tokens).compile()
    cost = compiled.cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    ours = spec.reference(config).flops_per_step(model, batch, seq) / 3
    assert 0.85 * xla <= ours <= xla, (ours, xla, ours / xla)


def test_published_size_counts():
    """2.517 GFLOP per token for mamba2-370m at 4 x 2048 tokens a step."""
    per_token = {}
    for name in SMALL:
        with open(os.path.join(spec.BENCH_DIR, "configs",
                               f"{name}.json")) as f:
            config = json.load(f)
        per_token[name] = spec.reference(config).flops_per_step(
            config["model"], 4, 2048) / (4 * 2048)
    assert per_token["mamba2-370m"] == pytest.approx(2.517e9, rel=1e-3)
