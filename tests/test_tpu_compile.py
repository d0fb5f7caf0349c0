"""The Pallas kernels compile for a described TPU v5e at the widths of
the configs that use them.  Nothing runs: the TPU compiler that ships
with jax compiles for a chip that is described, not attached, so these
catch what interpret mode cannot (block tiling, VMEM limits).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be read
    # back without one; keep these compiles out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    if not had_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("H,KVH,D,S,window", [
    (28, 4, 128, 4096, 0),      # qwen2-7b: GQA 7:1, full causal
    (8, 4, 256, 4096, 1024),    # gemma3-4b: head_dim 256, local window
], ids=["qwen2-7b", "gemma3-4b"])
def test_flash_attention_compiles_for_v5e(one_chip, H, KVH, D, S, window):
    fn = partial(flash_attention_pallas, causal=True, window=window,
                 interpret=False)
    compiled = _compile(fn, one_chip, ((1, H, S, D), jnp.bfloat16),
                        ((1, KVH, S, D), jnp.bfloat16),
                        ((1, KVH, S, D), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nh,N", [(32, 128), (64, 64)],
                         ids=["mamba2-370m", "zamba2-1.2b"])
def test_ssd_scan_compiles_for_v5e_at_mamba2_370m(one_chip, monkeypatch,
                                                  nh, N):
    """jax.grad of the model's SSD entry point at the configs' widths
    (heads of 64, chunk 256; one chip's 4 x 2048 tokens) takes the fused
    kernels, scoped ``ssd`` forward and backward, holds no (L, L, heads)
    f32 tensor, and needs fewer temporary bytes than the XLA scan."""
    import re

    from repro.kernels import platform
    from repro.models import ssm
    b, S, P = 4, 2048, 64

    def loss(*a):
        y, h = ssm.ssd(*a, 256, state=N, out_dtype=jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(h)

    shapes = [((b, S, nh * P + 2 * N), jnp.bfloat16),
              ((b, S, nh), jnp.float32), ((nh,), jnp.float32),
              ((nh,), jnp.float32)]
    temps = {}
    for kernel in (True, False):
        monkeypatch.setattr(platform, "on_tpu", lambda: kernel)
        # a new function each time: jit caches what it traced
        compiled = _compile(jax.grad(lambda *a: loss(*a), argnums=range(4)),
                            one_chip, *shapes)
        temps[kernel] = compiled.memory_analysis().temp_size_in_bytes
        text = compiled.as_text()
        calls = [ln for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        if kernel:
            assert len(calls) == 2                      # forward, backward
            for ln in calls:
                assert "ssd" in re.search(r'op_name="([^"]*)"', ln).group(1)
            assert not re.search(rf"f32\[[0-9,]*256,256,{nh}\]", text)
        else:
            assert not calls
            assert re.search(rf"f32\[[0-9,]*256,256,{nh}\]", text)
    assert temps[True] < temps[False]


def test_zamba2_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The benchmark's zamba2-1.2b train step (18 layers, 4 x 2048 tokens,
    one microbatch) compiles for one v5e chip: the shared block's ops are
    scoped ``shared_attn`` and ``shared_mlp``, the SSD takes the fused
    kernel pair (every custom call scoped ``ssd``, forward and, under a
    transpose, backward), and the arguments and temporaries
    (14.1 GB) fit the chip's 16 GB with room for the runtime."""
    import json
    import re

    from repro.kernels import platform
    from repro.models import init_params
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_train_step
    from repro.configs.base import ModelConfig, SSMConfig

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    with open(os.path.join(bench, "configs", "zamba2-1.2b.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "steady.json")) as f:
        traffic = json.load(f)
    model = dict(config["model"], ssm=SSMConfig(**config["model"]["ssm"]))
    cfg = ModelConfig(**model)
    ocfg = OptimizerConfig(**config["optimizer"])
    monkeypatch.setattr(platform, "on_tpu", lambda: True)

    def state(key):
        params = init_params(cfg, key)
        return params, init_opt_state(ocfg, params)

    params, opt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(state, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                  jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_train_step(cfg, ocfg),
                       donate_argnums=(0, 1)).lower(
        params, opt, {"tokens": tokens}).compile()
    text = compiled.as_text()
    for scope in ("shared_attn", "shared_mlp", "in_proj", "conv", "ssd",
                  "out_proj"):
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    assert names and all(re.search(r"[/(]ssd[/)]", n) for n in names)
    assert any("transpose(" in n for n in names)        # backward
    assert any("transpose(" not in n for n in names)    # forward
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
