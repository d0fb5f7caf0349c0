"""Kernel micro-benchmarks: XLA flash-attention path wall time on this
host (the Pallas kernels are interpret-mode-validated for correctness;
timings of interpret mode are not meaningful) + kernel-vs-oracle max
error as the correctness 'derived' column."""
from __future__ import annotations

import time

from benchmarks.common import Row, scaled


def _time(fn, *args, reps=3):
    import jax
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
        else fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def run(rows: Row) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.models.flash import flash_attention_xla

    key = jax.random.PRNGKey(0)
    S = scaled(512, 128)
    B, Sq, Sk, H, KVH, D = 2, S, S, 8, 4, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, KVH, D), jnp.float32)

    xla_fa = jax.jit(lambda q, k, v: flash_attention_xla(
        q, k, v, jnp.float32(jnp.inf), True, 128, 0.0, 0))
    us = _time(xla_fa, q, k, v)
    o_pal = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o_ref = ref.attention_ref(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o_pal - o_ref)))
    rows.add(f"flash_attention_xla_{Sq}", us,
             f"pallas_vs_ref_err={err:.2e}")

    b, S, nh, P, N = 2, S, 4, 32, 16
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, S, nh, P))
    Bm = jax.random.normal(ks[1], (b, S, N)) * 0.5
    Cm = jax.random.normal(ks[2], (b, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, S, nh)) - 1.0)
    A = -jnp.exp(jnp.zeros(nh))
    Dp = jnp.ones(nh)

    from repro.kernels.ssd_scan import ssd_pallas
    from repro.models.ssm import reference_scan, ssd_chunked
    xla_ssd = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    us = _time(xla_ssd, x, Bm, Cm, dt, A, Dp)
    xbc = jnp.concatenate([x.reshape(b, S, nh * P), Bm, Cm], axis=-1)
    y_pal, h_pal = ssd_pallas(jnp.swapaxes(xbc, 1, 2), dt, A, Dp, 128,
                              state=N)
    y_ref, h_ref = reference_scan(x, Bm, Cm, dt, A, Dp)
    err = float(jnp.max(jnp.abs(y_pal - y_ref)))
    rows.add(f"ssd_chunked_xla_{S}", us, f"pallas_vs_ref_err={err:.2e}")


if __name__ == "__main__":
    run(Row())
