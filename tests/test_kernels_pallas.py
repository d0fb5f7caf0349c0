"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes
and dtypes per the deliverable contract."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, platform, ref, ssd_scan
from repro.models import ssm
from repro.models.ssm import reference_scan, ssd_chunked

FLASH_CASES = [
    # B, Sq, Sk, H, KVH, D, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 64, 64, 4, 4, 32, True, 0, 0.0, jnp.float32),
    (1, 100, 144, 4, 4, 64, True, 32, 0.0, jnp.bfloat16),   # ragged + window
    (2, 64, 256, 8, 2, 128, False, 0, 0.0, jnp.float32),    # cross attn
    (1, 128, 128, 2, 1, 64, True, 0, 30.0, jnp.float32),    # softcap
    (1, 32, 32, 4, 2, 64, True, 8, 0.0, jnp.bfloat16),      # tiny blocks
]


def _qkv(B, Sq, Sk, H, KVH, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, KVH, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, KVH, D)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,Sq,Sk,H,KVH,D,causal,window,softcap,dtype", FLASH_CASES)
def test_flash_attention_matches_oracle(B, Sq, Sk, H, KVH, D, causal,
                                        window, softcap, dtype):
    q, k, v = _qkv(B, Sq, Sk, H, KVH, D, dtype)
    out = fa.flash_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, block_q=64, block_k=64,
                             interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - expected.astype(jnp.float32))))
    assert err < tol, f"err={err}"


FLASH_GRAD_CASES = [
    # B, S, H, KVH, D, causal, scale, dtype; blocks of 64 over 256 steps:
    # causal skips the 6 blocks above the diagonal and masks the 4 on it
    (1, 256, 2, 2, 128, True, None, jnp.float32),
    (2, 256, 2, 2, 128, True, 0.125, jnp.float32),     # zamba2's scale
    (1, 256, 4, 1, 64, True, None, jnp.float32),       # GQA 4:1
    (1, 256, 4, 2, 128, True, 0.125, jnp.bfloat16),    # GQA, bf16
    (1, 192, 2, 2, 64, False, 0.3, jnp.bfloat16),      # no mask at all
]


@pytest.mark.parametrize("B,S,H,KVH,D,causal,scale,dtype", FLASH_GRAD_CASES)
def test_flash_attention_grads_match_xla_and_naive(B, S, H, KVH, D, causal,
                                                   scale, dtype):
    """dq, dk and dv through the pair's custom VJP (interpret mode)
    against ``flash_attention_xla``'s and ``naive_attention``'s."""
    from repro.models.flash import flash_attention_xla
    from repro.models.layers import naive_attention
    q, k, v = _qkv(B, S, S, H, KVH, D, dtype, seed=7)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape).astype(dtype)

    def grads(fn):
        return jax.vjp(fn, q, k, v)[1](do)

    got = grads(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=64, block_k=64,
        interpret=True))
    xla = grads(lambda q, k, v: flash_attention_xla(
        q, k, v, jnp.float32(jnp.inf), causal, 64, 0.0, 0, scale))
    naive = grads(lambda q, k, v: naive_attention(
        q, k, v, causal=causal, scale=scale))
    # f32: the same sums in another order; bf16: the operands' rounding
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, g, x, n in zip(("dq", "dk", "dv"), got, xla, naive):
        assert g.shape == n.shape and g.dtype == n.dtype, name
        largest = float(jnp.max(jnp.abs(n.astype(jnp.float32))))
        assert _max_err(g, x) <= tol * largest, name
        assert _max_err(g, n) <= tol * largest, name


@pytest.mark.parametrize("Sq,Sk,D,fits", [
    (2048, 2048, 128, True),            # zamba2-1.2b
    (4096, 4096, 256, True),            # gemma3-4b
    (384, 384, 128, True),              # blocks of 128
    (2048, 2048, 64, False),            # heads of half a lane tile
    (100, 100, 128, False),             # no whole blocks
    (1500, 1500, 64, False),            # whisper's encoder
])
def test_flash_attention_takes_shapes_that_tile(Sq, Sk, D, fits):
    assert fa.tiles(Sq, Sk, D) is fits


@pytest.mark.parametrize("S,block", [(2048, 512), (768, 256), (384, 128),
                                     (100, 128)])
def test_flash_attention_blocks_follow_the_sequence(S, block):
    assert fa.block_size(S) == block


def _ssd_inputs(b, S, nh, P, N, dtype, seed=1):
    """x in ``dtype``; B and C f32 holding values of ``dtype``, as the
    model's conv output gives them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, S, nh, P)).astype(dtype)
    B = (jax.random.normal(ks[1], (b, S, N)) * 0.5).astype(dtype).astype(
        jnp.float32)
    C = (jax.random.normal(ks[2], (b, S, N)) * 0.5).astype(dtype).astype(
        jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, S, nh)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[4], (nh,)) * 0.5)
    D = jax.random.normal(ks[5], (nh,))
    return x, B, C, dt, A, D


def _xbc(x, B, C):
    """The conv output the model hands the SSD: [x | B | C] on one axis."""
    b, S, nh, P = x.shape
    return jnp.concatenate([x.reshape(b, S, nh * P), B.astype(x.dtype),
                            C.astype(x.dtype)], axis=-1)


def _kernel(chunk, **kw):
    """The fused kernel (interpret mode) with ssd_chunked's arguments."""
    def run(x, B, C, dt, A, D):
        return ssd_scan.ssd_pallas(jnp.swapaxes(_xbc(x, B, C), 1, 2), dt, A,
                                   D, chunk, state=B.shape[-1],
                                   interpret=True, **kw)
    return run


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


SSD_CASES = [
    # b, S, nh, P, N, chunk, dtype; the first four do not tile (chunks
    # under 128 steps): ``ssd`` takes ssd_chunked for them
    (2, 128, 4, 16, 8, 32, jnp.float32),
    (1, 256, 2, 32, 16, 64, jnp.float32),
    (1, 96, 3, 8, 4, 32, jnp.float32),       # S % chunk == 0, odd dims
    (2, 64, 4, 16, 8, 64, jnp.bfloat16),
    # the kernel's: 2 chunks of 128; heads of 64, 32 and 128
    (1, 256, 2, 64, 128, 128, jnp.float32),
    (1, 256, 2, 64, 128, 128, jnp.bfloat16),
    (2, 256, 4, 32, 64, 128, jnp.bfloat16),
    (1, 256, 1, 128, 16, 128, jnp.float32),
]


@pytest.mark.parametrize("b,S,nh,P,N,chunk,dtype", SSD_CASES)
def test_ssd_matches_oracle(b, S, nh, P, N, chunk, dtype):
    """The fused kernel (interpret mode) where the shapes tile, else the
    model's ``ssd`` entry point, against the step-by-step oracle."""
    x, B, C, dt, A, D = _ssd_inputs(b, S, nh, P, N, dtype)
    if ssd_scan.tiles(S, nh, P, N, chunk):
        y, h = _kernel(chunk)(x, B, C, dt, A, D)
        y_mid, h_mid = ssd_chunked(x, B, C, dt, A, D, chunk)
        assert _max_err(y, y_mid) < 1e-4
        assert _max_err(h, h_mid) < 1e-4
    else:
        y, h = ssm.ssd(_xbc(x, B, C), dt, A, D, chunk, state=N)
    y_ref, h_ref = reference_scan(x, B, C, dt, A, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    assert _max_err(y, y_ref) < tol
    assert _max_err(h, h_ref) < tol


SSD_GRAD_CASES = [
    # b, S, nh, P, N, chunk, x dtype, intra_dtype
    (1, 256, 2, 64, 128, 128, jnp.float32, jnp.float32),
    (1, 256, 2, 64, 128, 128, jnp.bfloat16, jnp.float32),
    (2, 256, 4, 32, 64, 128, jnp.bfloat16, jnp.bfloat16),
]


@pytest.mark.parametrize("b,S,nh,P,N,chunk,dtype,intra", SSD_GRAD_CASES)
def test_ssd_kernel_grads_match_oracles(b, S, nh, P, N, chunk, dtype, intra):
    """jax.grad through the kernel's custom VJP (w.r.t. x, B, C, dt, A, D,
    with cotangents on y and h_final) against ssd_chunked's autodiff and
    the step-by-step oracle's."""
    args = _ssd_inputs(b, S, nh, P, N, dtype)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    gy = jax.random.normal(ks[0], (b, S, nh, P))
    gh = jax.random.normal(ks[1], (b, nh, N, P))

    def loss(fn):
        def f(*a):
            y, h = fn(*a)
            return jnp.sum(y.astype(jnp.float32) * gy) + jnp.sum(h * gh)
        return jax.grad(f, argnums=range(6))

    kernel = loss(_kernel(chunk, intra_dtype=intra))
    chunked = loss(lambda *a: ssd_chunked(*a, chunk, intra_dtype=intra))
    oracle = loss(reference_scan)
    got, mid, ref = kernel(*args), chunked(*args), oracle(*args)
    # bf16 x rounds dx to bf16; bf16 intra rounds the terms of y
    tol_mid = 1e-4 if intra == jnp.float32 and dtype == jnp.float32 else 1e-2
    tol_ref = 1e-4 if intra == jnp.float32 and dtype == jnp.float32 else 3e-2
    for name, g, m, r in zip("x B C dt A D".split(), got, mid, ref):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        scale = float(jnp.max(jnp.abs(r.astype(jnp.float32))))
        assert _max_err(g, m) <= tol_mid * scale, name
        assert _max_err(g, r) <= tol_ref * scale, name


def test_model_ssd_chunked_matches_reference_scan():
    """The model-side chunked SSD (repro.models.ssm) against the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, S, nh, P, N = 2, 128, 4, 16, 8
    x = jax.random.normal(ks[0], (b, S, nh, P))
    B = jax.random.normal(ks[1], (b, S, N)) * 0.5
    C = jax.random.normal(ks[2], (b, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, S, nh)) - 1.0)
    A = -jnp.exp(jnp.zeros(nh))
    D = jnp.ones(nh)
    y1, h1 = ssd_chunked(x, B, C, dt, A, D, chunk=32)
    y2, h2 = reference_scan(x, B, C, dt, A, D)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-3
    assert float(jnp.max(jnp.abs(h1 - h2))) < 1e-3


@pytest.mark.parametrize("impl", ["model", "pallas"])
def test_ssd_finite_when_a_chunk_decays_far(impl):
    """Within a 256-step chunk sum(dt * A) reaches -1000, so exp of the
    above-diagonal (masked) differences overflows; outputs and
    gradients stay finite and the outputs match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    b, S, nh, P, N = 1, 256, 2, 64, 16
    x = jax.random.normal(ks[0], (b, S, nh, P))
    B = jax.random.normal(ks[1], (b, S, N)) * 0.5
    C = jax.random.normal(ks[2], (b, S, N)) * 0.5
    dt = jnp.full((b, S, nh), 0.5)
    A = -jnp.array([1.0, 8.0])
    D = jnp.ones(nh)
    if impl == "model":
        fn = partial(ssd_chunked, chunk=256)
    else:
        fn = _kernel(256)
    y, h = fn(x, B, C, dt, A, D)
    y_ref, h_ref = reference_scan(x, B, C, dt, A, D)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-3
    assert float(jnp.max(jnp.abs(h - h_ref))) < 1e-3
    grads = jax.grad(lambda *a: jnp.sum(fn(*a)[0]) + jnp.sum(fn(*a)[1]),
                     argnums=range(6))(x, B, C, dt, A, D)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("S,nh,P,N,chunk,fits", [
    (2048, 32, 64, 128, 256, True),     # mamba2-370m
    (2048, 64, 64, 64, 256, True),      # zamba2-1.2b
    (256, 1, 256, 16, 128, True),       # one head of 256
    (128, 4, 16, 16, 32, False),        # chunk under 128 steps
    (256, 2, 48, 128, 128, False),      # B and C not blocks of the conv out
    (256, 2, 24, 16, 128, False),       # heads of part of a bf16 tile
    (256, 2, 64, 12, 128, False),       # state of part of a bf16 tile
    (384, 2, 64, 128, 256, False),      # no whole chunks
])
def test_ssd_kernel_takes_shapes_that_tile(S, nh, P, N, chunk, fits):
    assert ssd_scan.tiles(S, nh, P, N, chunk) is fits


@pytest.mark.parametrize("on_tpu,policy,kernel", [
    (False, None, False),               # CPU: ssd_chunked
    (True, None, True),                 # a TPU, one device
    (True, ("data",), False),           # a sharding policy is active
])
def test_ssd_path_follows_backend_and_sharding(monkeypatch, on_tpu, policy,
                                               kernel):
    """What ``ssd`` takes, seen by tracing it (nothing runs)."""
    from repro.distributed import sharding
    monkeypatch.setattr(platform, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(sharding, "_ACTIVATION_AXES", [policy])
    args = _ssd_inputs(1, 256, 2, 64, 128, jnp.bfloat16)
    with ssm.ssd_paths() as taken:
        jax.eval_shape(lambda x, B, C, *a: ssm.ssd(_xbc(x, B, C), *a, 128,
                                                   state=128), *args)
    assert taken == [kernel]


def test_ssd_fallback_is_ssd_chunked_and_the_trainer_reads_zero(tmp_path):
    """On the CPU ``ssd`` is ssd_chunked, value for value, and a
    Trainer's ``model.ssd_kernel`` gauge reads 0 after its step traced."""
    from repro.configs import get_config
    from repro.train.trainer import Trainer, TrainerConfig
    x, B, C, dt, A, D = _ssd_inputs(1, 256, 2, 64, 128, jnp.bfloat16)
    y, h = ssm.ssd(_xbc(x, B, C), dt, A, D, 128, state=128)
    y_mid, h_mid = ssd_chunked(x, B, C, dt, A, D, 128)
    assert bool(jnp.all(y == y_mid)) and bool(jnp.all(h == h_mid))

    cfg = get_config("mamba2-370m", reduced=True)
    rng = np.random.default_rng(0)
    batches = (rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
               for _ in range(1))
    trainer = Trainer(cfg, TrainerConfig(steps=1, checkpoint_every=100,
                                         log_every=100,
                                         checkpoint_dir=str(tmp_path)),
                      batches)
    gauges = trainer.run()["telemetry"]["gauges"]
    assert gauges["model.ssd_kernel"] == 0.0
    assert gauges["model.attention_kernel"] == 0.0       # no attention


def _attention_cfg(impl="blocked"):
    from repro.configs import get_config
    return get_config("zamba2-1.2b", reduced=True).replace(
        attention_impl=impl)


@pytest.mark.parametrize("on_tpu,policy,S,D,impl,kw,kernel", [
    (False, None, 256, 128, "blocked", {}, False),      # CPU: XLA path
    (True, None, 256, 128, "blocked", {}, True),        # a TPU, one device
    (True, ("data",), 256, 128, "blocked", {}, False),  # sharding policy
    (True, None, 200, 128, "blocked", {}, False),       # no whole blocks
    (True, None, 256, 64, "blocked", {}, False),        # half a lane tile
    (True, None, 256, 128, "blocked", {"window": 64}, True),
    (True, None, 256, 128, "blocked",                   # per-layer window
     {"window": jnp.int32(64)}, False),
    (True, None, 256, 128, "naive", {}, False),
    (False, None, 200, 64, "pallas", {}, True),         # the pair, padded
])
def test_attention_path_follows_backend_sharding_and_shape(
        monkeypatch, on_tpu, policy, S, D, impl, kw, kernel):
    """What ``attention`` takes, seen by tracing it (nothing runs)."""
    from repro.distributed import sharding
    from repro.models import layers
    monkeypatch.setattr(platform, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(sharding, "_ACTIVATION_AXES", [policy])
    q, k, v = _qkv(1, S, S, 2, 2, D, jnp.bfloat16)
    with layers.attention_paths() as taken:
        jax.eval_shape(lambda q, k, v: layers.attention(
            _attention_cfg(impl), q, k, v, causal=True, scale=0.125, **kw),
            q, k, v)
    assert taken == [kernel]


def test_attention_fallback_is_flash_xla_and_the_trainer_reads_zero(
        tmp_path):
    """On the CPU ``attention`` is ``flash_attention_xla``, value for
    value, decode steps log no path, and a reduced zamba2 Trainer's
    ``model.attention_kernel`` gauge reads 0 after its step traced."""
    from repro.models import layers
    from repro.models.flash import flash_attention_xla
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = _attention_cfg()
    q, k, v = _qkv(2, 256, 256, 4, 2, 128, jnp.bfloat16)
    with layers.attention_paths() as taken:
        out = layers.attention(cfg, q, k, v, causal=True, scale=0.125)
        layers.attention(cfg, q[:, :1], k, v, causal=False, q_offset=255)
    xla = flash_attention_xla(q, k, v, jnp.float32(jnp.inf), True,
                              cfg.attention_block_k, 0.0, 0, 0.125)
    assert bool(jnp.all(out == xla))
    assert taken == [False]

    rng = np.random.default_rng(0)
    batches = (rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
               for _ in range(1))
    trainer = Trainer(cfg, TrainerConfig(steps=1, checkpoint_every=100,
                                         log_every=100,
                                         checkpoint_dir=str(tmp_path)),
                      batches)
    gauges = trainer.run()["telemetry"]["gauges"]
    assert gauges["model.attention_kernel"] == 0.0
    assert gauges["model.shared_sites"] == 2.0


def test_flash_xla_custom_vjp_grads_match_naive():
    from repro.models.flash import flash_attention_xla
    from repro.models.layers import naive_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, Sq, Sk, H, KVH, D = 2, 64, 64, 4, 2, 32
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, KVH, D))
    v = jax.random.normal(ks[2], (B, Sk, KVH, D))
    win = jnp.float32(16.0)

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_xla(q, k, v, win, True,
                                                   32, 0.0, 0)))

    def f_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_attention(q, k, v, causal=True,
                                               window=16)))

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
