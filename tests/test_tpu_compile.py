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
from repro.kernels.ssd_scan import ssd_scan


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


def test_ssd_scan_compiles_for_v5e_at_mamba2_370m(one_chip):
    # mamba2-370m: 32 heads of head_dim 64, state 128, chunk 256
    b, S, nh, P, N = 2, 512, 32, 64, 128
    fn = partial(ssd_scan, chunk=256, interpret=False)
    compiled = _compile(fn, one_chip,
                        ((b, S, nh, P), jnp.bfloat16),
                        ((b, S, N), jnp.float32), ((b, S, N), jnp.float32),
                        ((b, S, nh), jnp.float32), ((nh,), jnp.float32),
                        ((nh,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
