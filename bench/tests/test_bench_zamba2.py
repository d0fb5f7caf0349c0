"""The zamba2-1.2b configuration and its reference: the weights fit the
program's tree, and a whole run of the harness on the CPU at a tiny size
with two shared-block sites is sound and correct, against which the
control and the fault are not.  Its FLOP count: against XLA's cost
analysis at a size where every loop runs once, at the published size, and
by hand."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import tiny
from harness import cell as cell_lib, check, spec

NAME = "zamba2-1.2b"
# two sites (layers 2 and 4) at a size the CPU runs in seconds
TINY_MODEL = dict(n_layers=5, shared_attn_every=2, n_heads=4, n_kv_heads=4,
                  head_dim=32, d_ff=128, adapter_rank=8)


def config() -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def tiny_cell() -> spec.Cell:
    cell = tiny.tiny_cell(NAME, "steady", f"{NAME}.steady")
    cell.config["model"].update(TINY_MODEL)
    return cell


def test_the_weights_fit_the_programs_tree():
    from repro.models import init_params
    model = tiny_cell().config["model"]
    ours = jax.eval_shape(lambda k: spec.reference(config()).init(model, k),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: init_params(cell_lib.model_config(model), k),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops": 1e12})
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    return lambda: cell_lib.run_cell(tiny_cell(), 2**31 + 77, 0.5, False,
                                     str(tmp_path / "work"), 0.0)


def test_a_sound_run_is_correct(cpu_run):
    out = cpu_run()
    assert all(n.ok for n in out.numbers), check.format_numbers(out.numbers)
    assert out.window_compiles == 0 and len(out.run.window_steps) > 0


@pytest.mark.parametrize("reading", ["control_fp8", "fault_half_batch"])
def test_the_control_and_the_fault_are_not_correct(reading):
    import readings
    line = next(x for x in readings.readings(tiny_cell(), 2**31 + 5)
                if x["reading"] == reading)
    assert line["correct"] is False


# one Mamba layer, then one site and its hybrid layer: one SSD chunk, one
# attention block and no vocabulary chunks, so that XLA counts each
# operation once
SMALL = dict(n_layers=2, shared_attn_every=1, d_model=256, n_heads=4,
             n_kv_heads=4, head_dim=128, d_ff=1024, adapter_rank=32,
             vocab_size=1024, vocab_chunk=0, remat="none")


def test_flop_count_matches_xla_cost_analysis():
    """XLA also counts the elementwise work, which the model count leaves
    out, and attention's full square, where the model counts its causal
    half, so the model count sits a little below it."""
    from repro.models import init_params, loss_fn
    model = config()["model"]
    model.update(SMALL)
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
    ours = spec.reference(config()).flops_per_step(model, batch, seq) / 3
    assert 0.85 * xla <= ours <= xla, (ours, xla, ours / xla)


def test_published_size_count():
    """4.81 GFLOP per token at 4 x 2048 tokens a step."""
    model = config()["model"]
    per_token = spec.reference(config()).flops_per_step(model, 4, 2048) / (
        4 * 2048)
    assert per_token == pytest.approx(4.81e9, rel=2e-3)


def test_count_by_hand():
    """Forward times 3 at 18 layers, of which the two shared-block sites
    are 31%: per token, each Mamba layer's in_proj, the SSD's dual form
    over a 256 chunk and its states, and out_proj; each site's q, k, v
    (4096 -> 32 x 128), output, gate-up with the rank-128 adapter, down
    and 2048 x 2048 linear, and attention over the causal half; the
    logits over the 2047 of 2048 positions that have a target."""
    d, di, n, nh, chunk, seq = 2048, 4096, 64, 64, 256, 2048
    mamba = 2 * (d * (2 * di + 2 * n + nh) + chunk * n + chunk * di
                 + 2 * n * di + di * d)
    site = 2 * (3 * 4096 * 4096 + 4096 * d + d * 16384 + d * 128
                + 128 * 16384 + 8192 * d + d * d) + 2 * 2 * (seq // 2) * 4096
    logits = 2 * d * 32000 * (seq - 1) / seq
    fwd = 18 * mamba + 2 * site + logits
    got = spec.reference(config()).flops_per_step(config()["model"], 4,
                                                  seq) / (4 * seq)
    assert got == pytest.approx(3 * fwd, rel=1e-9)
    assert 2 * site / fwd == pytest.approx(0.31, abs=0.005)
