"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes
and dtypes per the deliverable contract."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas

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


SSD_CASES = [
    # b, S, nh, P, N, chunk, dtype
    (2, 128, 4, 16, 8, 32, jnp.float32),
    (1, 256, 2, 32, 16, 64, jnp.float32),
    (1, 96, 3, 8, 4, 32, jnp.float32),       # S % chunk == 0, odd dims
    (2, 64, 4, 16, 8, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("b,S,nh,P,N,chunk,dtype", SSD_CASES)
def test_ssd_matches_oracle(b, S, nh, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (b, S, nh, P)).astype(dtype)
    B = (jax.random.normal(ks[1], (b, S, N)) * 0.5).astype(jnp.float32)
    C = (jax.random.normal(ks[2], (b, S, N)) * 0.5).astype(jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, S, nh)) - 1.0)
    A = -jnp.exp(jnp.zeros(nh))
    D = jnp.ones(nh)
    y, h = ops.ssd(x, B, C, dt, A, D, chunk=chunk)
    y_ref, h_ref = ref.ssd_ref(x, B, C, dt, A, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    assert float(jnp.max(jnp.abs(y - y_ref))) < tol
    assert float(jnp.max(jnp.abs(h - h_ref))) < tol


def test_model_ssd_chunked_matches_reference_scan():
    """The model-side chunked SSD (repro.models.ssm) against the oracle."""
    from repro.models.ssm import reference_scan, ssd_chunked
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
    above-diagonal (masked) differences overflows; outputs and the
    model path's gradients stay finite and match the oracle."""
    from repro.models.ssm import reference_scan, ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    b, S, nh, P, N = 1, 256, 2, 8, 4
    x = jax.random.normal(ks[0], (b, S, nh, P))
    B = jax.random.normal(ks[1], (b, S, N)) * 0.5
    C = jax.random.normal(ks[2], (b, S, N)) * 0.5
    dt = jnp.full((b, S, nh), 0.5)
    A = -jnp.array([1.0, 8.0])
    D = jnp.ones(nh)
    fn = ssd_chunked if impl == "model" else ops.ssd
    y, h = fn(x, B, C, dt, A, D, chunk=256)
    y_ref, h_ref = reference_scan(x, B, C, dt, A, D)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-3
    assert float(jnp.max(jnp.abs(h - h_ref))) < 1e-3
    if impl == "model":
        g = jax.grad(lambda a: jnp.sum(
            ssd_chunked(x, B, C, dt, a, D, chunk=256)[0]))(A)
        assert bool(jnp.all(jnp.isfinite(g)))


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
