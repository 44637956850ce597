"""Per-kernel allclose vs ref.py oracles across shape/dtype sweeps
(interpret=True executes the kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, paged_attention, ssd_scan
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(7)


def tol_for(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- paged
@pytest.mark.parametrize("B,H,KVH,D,ps,maxp", [
    (2, 4, 1, 32, 8, 3),
    (3, 8, 2, 64, 16, 4),
    (1, 12, 4, 128, 32, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, KVH, D, ps, maxp, dtype):
    ks = jax.random.split(KEY, 4)
    P = B * maxp + 1
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (P, ps, KVH, D), dtype)
    vp = jax.random.normal(ks[2], (P, ps, KVH, D), dtype)
    pt = jax.random.permutation(ks[3], np.arange(P))[: B * maxp] \
        .reshape(B, maxp).astype(jnp.int32)
    lengths = jnp.asarray(
        [1 + (i * 7) % (ps * maxp) for i in range(B)], jnp.int32)
    out = paged_attention(q, kp, vp, pt, lengths, scale=D ** -0.5,
                          interpret=True)
    ref = R.ref_paged_attention(q, kp, vp, pt, lengths, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol_for(dtype))


def test_paged_attention_softcap():
    B, H, KVH, D, ps, maxp = 2, 4, 2, 32, 8, 3
    ks = jax.random.split(KEY, 4)
    P = B * maxp
    q = jax.random.normal(ks[0], (B, H, D)) * 3
    kp = jax.random.normal(ks[1], (P, ps, KVH, D))
    vp = jax.random.normal(ks[2], (P, ps, KVH, D))
    pt = jnp.arange(P, dtype=jnp.int32).reshape(B, maxp)
    lengths = jnp.asarray([20, 9], jnp.int32)
    out = paged_attention(q, kp, vp, pt, lengths, scale=0.2, softcap=30.0,
                          interpret=True)
    ref = R.ref_paged_attention(q, kp, vp, pt, lengths, scale=0.2,
                                softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("S,H,KVH,D,bq,bk", [
    (64, 4, 2, 32, 16, 16),
    (100, 4, 4, 64, 32, 16),   # ragged tail
    (33, 8, 2, 128, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, KVH, D, bq, bk, dtype):
    B = 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, D), dtype)
    out = flash_attention(q, k, v, scale=D ** -0.5, block_q=bq, block_kv=bk,
                          interpret=True)
    ref = R.ref_flash_attention(q, k, v, scale=D ** -0.5, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol_for(dtype))


@pytest.mark.parametrize("window,softcap,causal", [
    (16, 0.0, True), (0, 25.0, True), (16, 25.0, True), (0, 0.0, False)])
def test_flash_attention_variants(window, softcap, causal):
    B, S, H, KVH, D = 1, 80, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    out = flash_attention(q, k, v, scale=0.2, causal=causal, window=window,
                          softcap=softcap, block_q=16, block_kv=16,
                          interpret=True)
    ref = R.ref_flash_attention(q, k, v, scale=0.2, causal=causal,
                                window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ ssd
@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (64, 2, 16, 1, 16, 16),
    (70, 4, 16, 2, 32, 32),    # ragged tail + grouped B/C
    (32, 8, 64, 1, 128, 8),
])
def test_ssd_scan_sweep(S, H, P, G, N, chunk):
    B = 2
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, finr = R.ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               rtol=1e-3, atol=1e-3)


def test_ssd_scan_initial_state():
    """Carrying a nonzero initial state (prefill-with-cache path)."""
    B, S, H, P, G, N = 1, 40, 2, 16, 1, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    init = jax.random.normal(jax.random.PRNGKey(9), (B, H, P, N))
    y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=16, initial_state=init,
                      interpret=True)
    yr, finr = R.ref_ssd(x, dt, A, Bm, Cm, initial_state=init)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("kernel", ["flash", "paged", "ssd"])
def test_kernels_refuse_the_cpu_without_interpret(kernel):
    """Interpret mode runs only when asked for: off the TPU a kernel call
    without interpret=True raises instead of falling back."""
    z = jnp.zeros
    with pytest.raises(ValueError, match="interpret"):
        if kernel == "flash":
            q = z((1, 16, 2, 32))
            flash_attention(q, q, q, scale=0.2)
        elif kernel == "paged":
            pages = z((2, 8, 1, 32))
            paged_attention(z((1, 2, 32)), pages, pages,
                            jnp.zeros((1, 2), jnp.int32),
                            jnp.ones((1,), jnp.int32), scale=0.2)
        else:
            ssd_scan(z((1, 16, 2, 16)), z((1, 16, 2)), -jnp.ones((2,)),
                     z((1, 16, 1, 16)), z((1, 16, 1, 16)), chunk=8)


def test_kernels_match_model_layers(rng_key):
    """Cross-check: the Pallas flash kernel agrees with the model's XLA
    blocked_attention (same math, different engines)."""
    from repro.models.layers import blocked_attention
    B, S, H, KVH, D = 1, 48, 4, 2, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    a = flash_attention(q, k, v, scale=0.25, block_q=16, block_kv=16,
                        interpret=True)
    b = blocked_attention(q, k, v, causal=True, scale=0.25,
                          block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
