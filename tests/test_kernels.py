"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """Interpret on the CPU, compile on a TPU, and no silent fallback to
    the interpreter anywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# ---------------------------------------------------------------------------
# cache_sim
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_sets,n_ways,n,chunk", [
    (16, 2, 256, 64), (64, 4, 1024, 256), (128, 8, 555, 128),
    (32, 1, 333, 512),
])
def test_cache_sim_matches_ref(n_sets, n_ways, n, chunk):
    addr = jnp.asarray(RNG.integers(0, n_sets * n_ways * 4, n), jnp.int32)
    h1, t1, u1 = ops.cache_sim(addr, n_sets=n_sets, n_ways=n_ways,
                               chunk=chunk)
    h2, t2, u2 = ref.cache_sim(addr, n_sets, n_ways)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    # sentinel padding is gated in-kernel: final state matches even when
    # the trace is not a chunk multiple
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 511), min_size=1, max_size=300))
def test_cache_sim_property(addrs):
    addr = jnp.asarray(addrs, jnp.int32)
    h1, _, _ = ops.cache_sim(addr, n_sets=16, n_ways=4, chunk=128)
    h2, _, _ = ref.cache_sim(addr, 16, 4)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


# ---------------------------------------------------------------------------
# stream_triad
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((8, 128), jnp.float32), ((32, 256), jnp.float32),
    ((16, 128), jnp.bfloat16), ((64, 512), jnp.float32),
])
def test_triad(shape, dtype):
    b, c = randn(shape, dtype), randn(shape, dtype)
    got = ops.stream_triad(b, c, 2.5)
    want = ref.stream_triad(b, c, 2.5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,sq,sk,d,win,dtype", [
    (2, 4, 128, 128, 64, None, jnp.float32),
    (1, 2, 128, 256, 64, None, jnp.float32),       # decode-style offset
    (2, 4, 256, 256, 64, 64, jnp.float32),          # sliding window
    (1, 2, 128, 128, 128, None, jnp.bfloat16),
    (1, 8, 384, 384, 32, 128, jnp.float32),
])
def test_flash_attention(b, h, sq, sk, d, win, dtype):
    q, k, v = (randn((b, h, sq, d), dtype), randn((b, h, sk, d), dtype),
               randn((b, h, sk, d), dtype))
    got = ops.flash_attention(q, k, v, causal=True, window=win)
    want = ref.flash_attention(q, k, v, causal=True, window=win)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kh,d,page,nblk,pool", [
    (2, 8, 2, 64, 16, 4, 16),
    (4, 4, 4, 32, 8, 8, 64),       # MHA
    (1, 16, 2, 128, 32, 2, 8),
])
def test_paged_attention(b, h, kh, d, page, nblk, pool):
    q = randn((b, h, d))
    kp = randn((pool, page, kh, d))
    vp = randn((pool, page, kh, d))
    bt = jnp.asarray(RNG.integers(0, pool, (b, nblk)), jnp.int32)
    cl = jnp.asarray(RNG.integers(1, page * nblk + 1, (b,)), jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, cl)
    want = ref.paged_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_paged_attention_empty_context():
    q = randn((1, 4, 32))
    kp = randn((4, 8, 2, 32))
    vp = randn((4, 8, 2, 32))
    bt = jnp.zeros((1, 2), jnp.int32)
    cl = jnp.zeros((1,), jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, cl)
    assert bool(jnp.isfinite(out).all())


# flash == paged on equivalent layouts (cross-kernel consistency)
def test_flash_paged_consistency():
    b, h, kh, d, page, nblk = 2, 8, 2, 64, 16, 4
    s = page * nblk
    kp = randn((b * nblk, page, kh, d))
    vp = randn((b * nblk, page, kh, d))
    bt = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    cl = jnp.full((b,), s, jnp.int32)
    q = randn((b, h, d))
    got = ops.paged_attention(q, kp, vp, bt, cl)
    # dense equivalent
    k = kp.reshape(b, s, kh, d)
    v = vp.reshape(b, s, kh, d)
    kx = jnp.repeat(k, h // kh, axis=2).transpose(0, 2, 1, 3)
    vx = jnp.repeat(v, h // kh, axis=2).transpose(0, 2, 1, 3)
    want = ref.flash_attention(q[:, :, None, :], kx, vx, causal=True)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
