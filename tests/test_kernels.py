"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property sweeps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_reference
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.rmsnorm.kernel import fused_residual_rmsnorm
from repro.kernels.rmsnorm.ref import fused_residual_rmsnorm_reference
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd import ref as ssd_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 64),        # MHA
    (2, 8, 2, 256, 64),        # GQA 4:1
    (1, 4, 1, 256, 128),       # MQA
    (1, 2, 2, 512, 128),       # longer seq
    (1, 56, 8, 128, 128),      # llava head geometry
])
def test_flash_attention_sweep(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    ref = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_independence():
    """Different BlockSpec tilings must give identical results."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    o1 = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    o2 = flash_attention(q, k, v, bq=256, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


@given(s_pow=st.integers(1, 3), d=st.sampled_from([64, 128]),
       g=st.sampled_from([1, 2, 4]))
@settings(deadline=None, max_examples=8)
def test_flash_attention_property(s_pow, d, g):
    s = 128 * s_pow
    ks = jax.random.split(jax.random.PRNGKey(s + d + g), 3)
    q = jax.random.normal(ks[0], (1, 2 * g, s, d))
    k = jax.random.normal(ks[1], (1, 2, s, d))
    v = jax.random.normal(ks[2], (1, 2, s, d))
    out = flash_attention(q, k, v, interpret=True)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,s,d,pos", [
    (2, 4, 2, 1024, 64, 700),
    (1, 8, 8, 512, 128, 0),        # first token
    (1, 16, 2, 2048, 64, 2047),    # full cache
    (4, 4, 1, 512, 128, 333),
])
def test_decode_attention_sweep(b, hq, hkv, s, d, pos, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = decode_attention(q, k, v, pos, bk=256, interpret=True)
    ref = decode_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@given(pos=st.integers(0, 511), bk=st.sampled_from([128, 256, 512]))
@settings(deadline=None, max_examples=10)
def test_decode_attention_any_position(pos, bk):
    ks = jax.random.split(jax.random.PRNGKey(pos), 3)
    q = jax.random.normal(ks[0], (1, 4, 1, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    out = decode_attention(q, k, v, pos, bk=bk, interpret=True)
    ref = decode_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 128, 64),      # full mamba2-370m head geometry
    (1, 96, 2, 16, 16, 32),        # padded tail (96 % 32 == 0 but try 40)
    (1, 100, 2, 16, 16, 32),       # non-multiple sequence (internal pad)
])
def test_ssd_kernel_sweep(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, h, n), dtype)
    C = jax.random.normal(ks[4], (b, s, h, n), dtype)
    y, state = ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, state_ref = ssd_ref.ssd_sequential(x, dt, A, B, C)
    yr = np.asarray(y_ref, np.float32)
    # bf16 tolerance scales with output magnitude (state dim N accumulation)
    rt = (dict(rtol=4e-2, atol=4e-2 + 0.02 * np.abs(yr).max())
          if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4))
    np.testing.assert_allclose(np.asarray(y, np.float32), yr, **rt)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunked_xla_matches_sequential_long():
    """The XLA lowering used by the dry-run agrees with the recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, s, h, p, n = 1, 512, 2, 32, 32
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, h, n))
    C = jax.random.normal(ks[4], (b, s, h, n))
    y1, s1 = ssd_ref.ssd_chunked(x, dt, A, B, C, chunk=128)
    y2, s2 = ssd_ref.ssd_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=3e-4, atol=3e-4)


def test_ssd_chunked_gradient_finite_under_strong_decay():
    """At the init's strongest decay (A = -16) and dt = 0.1, a 256-step
    chunk's segment sums reach exp(+409) above the diagonal: the masked
    entries must not turn the backward pass into inf * 0 = NaN."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, s, h, p, n = 1, 512, 2, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    B = jax.random.normal(ks[1], (b, s, h, n))
    C = jax.random.normal(ks[2], (b, s, h, n))
    A = jnp.full((h,), -16.0)
    grads = jax.grad(
        lambda dt, A: ssd_ref.ssd_chunked(x, dt, A, B, C, chunk=256)[0].sum(),
        argnums=(0, 1))(jnp.full((b, s, h), 0.1), A)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@given(chunk=st.sampled_from([16, 32, 64]), s_mult=st.integers(2, 6))
@settings(deadline=None, max_examples=8)
def test_ssd_chunk_size_invariance(chunk, s_mult):
    """Output must not depend on the chunking (algebraic identity)."""
    s = chunk * s_mult
    ks = jax.random.split(jax.random.PRNGKey(chunk * s), 5)
    x = jax.random.normal(ks[0], (1, s, 2, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)))
    B = jax.random.normal(ks[3], (1, s, 2, 16))
    C = jax.random.normal(ks[4], (1, s, 2, 16))
    y1, s1 = ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, s2 = ssd_ref.ssd_chunked(x, dt, A, B, C, chunk=s)   # one big chunk
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# fused rmsnorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("r,d", [(64, 128), (100, 256), (1000, 512),
                                 (7, 1024)])
def test_rmsnorm_sweep(r, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (r, d), dtype)
    res = jax.random.normal(ks[1], (r, d), dtype)
    sc = jax.random.normal(ks[2], (d,), jnp.float32)
    y, new_res = fused_residual_rmsnorm(x, res, sc, block_rows=32,
                                        interpret=True)
    y_ref, res_ref = fused_residual_rmsnorm_reference(x, res, sc)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(new_res, np.float32),
                               np.asarray(res_ref, np.float32), **tol(dtype))
