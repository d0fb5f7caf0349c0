"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes
and dtypes per the deliverable contract."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import platform, ref, ssd_scan
from repro.kernels.flash_attention import flash_attention_pallas
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


@pytest.mark.parametrize(
    "B,Sq,Sk,H,KVH,D,causal,window,softcap,dtype", FLASH_CASES)
def test_flash_attention_matches_oracle(B, Sq, Sk, H, KVH, D, causal,
                                        window, softcap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KVH, Sk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KVH, Sk, D)).astype(dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, block_q=64, block_k=64,
                                 interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - expected.astype(jnp.float32))))
    assert err < tol, f"err={err}"


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
    out = trainer.run()
    assert out["telemetry"]["gauges"]["model.ssd_kernel"] == 0.0


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
