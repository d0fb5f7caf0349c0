"""Sharding rules + a miniature multi-device dry-run.

The mini dry-run runs in a SUBPROCESS because the 8-placeholder-device
XLA flag must be set before jax initializes (the main pytest process
keeps 1 device, per the assignment)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch.specs import params_struct


class FakeMesh:
    """Duck-typed mesh for spec tests (axis sizes only)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def devices(self):
        import numpy as np
        return np.empty((1,))


MESH = FakeMesh({"data": 16, "model": 16})


@pytest.mark.parametrize("arch", ["qwen2-7b", "llama-3.2-vision-90b",
                                  "dbrx-132b", "mamba2-370m",
                                  "zamba2-1.2b", "whisper-tiny"])
def test_param_specs_are_divisible(arch):
    cfg = get_config(arch)
    pshape = params_struct(cfg)
    specs = shd.param_specs(cfg, pshape, MESH)

    def check(leaf, spec):
        assert len(spec) <= len(leaf.shape)
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if entry is None:
                continue
            n = shd.axis_size(MESH, entry)
            assert dim % n == 0, f"{arch}: {leaf.shape} vs {spec}"

    jax.tree.map(check, pshape, specs,
                 is_leaf=lambda x: hasattr(x, "shape"))


def test_head_indivisible_archs_replicate_heads():
    cfg = get_config("qwen2-7b")           # 28 heads, model axis 16
    pshape = params_struct(cfg)
    specs = shd.param_specs(cfg, pshape, MESH)
    wq_spec = specs["stack"]["layers"]["attn"]["wq"]
    assert wq_spec[2] is None              # head dim not sharded
    assert wq_spec[1] is not None          # but FSDP on d_model applies


def test_moe_expert_sharding_modes():
    import dataclasses
    cfg = get_config("dbrx-132b")
    pshape = params_struct(cfg)
    tp = shd.param_specs(cfg, pshape, MESH)
    w1 = tp["stack"]["layers"]["moe"]["w1"]
    assert w1[3] == "model"                # ffn sharded (tp mode)
    cfg_ep = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                 expert_sharding="ep"))
    ep = shd.param_specs(cfg_ep, pshape, MESH)
    w1e = ep["stack"]["layers"]["moe"]["w1"]
    assert w1e[1] == "model"               # expert dim sharded (ep mode)


def test_cache_specs_fall_back_to_sequence_parallel():
    from repro.configs import SHAPES_BY_NAME
    cfg = get_config("gemma3-12b")         # kv=8 < model 16 -> SP on seq
    specs = shd.cache_specs(cfg, SHAPES_BY_NAME["decode_32k"], MESH)
    assert specs["k"][2] is not None       # seq dim sharded
    assert specs["k"][3] is None
    cfg2 = get_config("zamba2-1.2b")       # kv=32 divisible -> head shard
    specs2 = shd.cache_specs(cfg2, SHAPES_BY_NAME["decode_32k"], MESH)
    assert specs2["shared_k"][3] == "model"


def test_zamba2_shared_block_shards_its_4096_wide_heads():
    """The shared block reads [hidden | embedding], 4096 wide, into 32
    heads of 128: q, k, v shard their heads over "model" and their input
    over the batch axes; the per-site caches (sites, B, S, 32, 128) shard
    their heads."""
    from repro.configs import SHAPES_BY_NAME
    from repro.models import init_cache
    cfg = get_config("zamba2-1.2b")
    pshape = params_struct(cfg)
    specs = shd.param_specs(cfg, pshape, MESH)["stack"]
    for name in ("wq", "wk", "wv"):
        assert pshape["stack"]["shared"]["attn"][name].shape == (4096, 32, 128)
        assert tuple(specs["shared"]["attn"][name]) == ("data", "model", None)
    assert tuple(specs["shared"]["attn"]["wo"]) == ("model", None, "data")
    assert tuple(specs["sites"]["adapter_b"]) == (None, None, None, "model")
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 64))
    assert cache["shared_k"].shape == (6, 2, 64, 32, 128)
    kv = shd.cache_specs(cfg, SHAPES_BY_NAME["decode_32k"], MESH)["shared_k"]
    assert len(kv) == 5 and kv[3] == "model"


def test_shard_batch_noop_without_policy():
    x = jnp.ones((4, 8))
    assert shd.shard_batch(x) is x


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, "src")
    import jax
    from repro.configs import get_config, SHAPES_BY_NAME
    from repro.distributed import sharding as shd
    from repro.launch.dryrun import build_cell
    import dataclasses

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("{arch}", reduced=True)
    shape = dataclasses.replace(SHAPES_BY_NAME["{shape}"],
                                seq_len=64, global_batch=8)
    shd.set_activation_axes(shd.batch_axes(mesh), mesh=mesh)
    jitted, args, extra = build_cell(cfg, shape, mesh)
    with mesh:
        compiled = jitted.lower(*args).compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    print(json.dumps({{"ok": True,
                       "temp": ma.temp_size_in_bytes,
                       "flops": ca.get("flops", 0.0)}}))
""")


@pytest.mark.parametrize("arch,shape", [
    ("qwen2-7b", "train_4k"),
    ("dbrx-132b", "train_4k"),
    ("mamba2-370m", "decode_32k"),
    ("whisper-tiny", "prefill_32k"),
    ("zamba2-1.2b", "train_4k"),
    ("zamba2-1.2b", "decode_32k"),
])
def test_mini_dryrun_compiles_on_8_devices(arch, shape):
    code = MINI_DRYRUN.format(arch=arch, shape=shape)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["flops"] > 0
