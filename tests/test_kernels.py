"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import ref_decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import ref_attention
from repro.kernels.fused_preprocess import fused_preprocess
from repro.kernels.fused_preprocess.ref import ref_preprocess
from repro.kernels.ssd_scan import ssd
from repro.kernels.ssd_scan.ref import ref_ssd

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D,window,bq,bk", [
    (2, 256, 4, 2, 64, 0, 128, 128),
    (1, 512, 8, 1, 128, 0, 128, 256),    # MQA
    (2, 256, 4, 4, 64, 96, 64, 64),      # sliding window
    (1, 384, 6, 2, 32, 0, 128, 128),     # non-pow2 heads, padded seq
])
def test_flash_attention_sweep(dtype, B, S, H, Hkv, D, window, bq, bk, rng):
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    got = flash_attention(q, k, v, True, window, None, bq, bk, True)
    want = ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_grad_matches_ref(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)

    def f_kern(q_):
        return flash_attention(q_, k, v, True, 0, None, 64, 64, True).sum()

    def f_ref(q_):
        return ref_attention(q_, k, v, causal=True).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f_kern)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,T,pos,window,bt", [
    (2, 4, 2, 64, 512, 100, 0, 128),
    (1, 8, 8, 128, 1024, 1023, 0, 256),
    (2, 4, 1, 64, 256, 300, 256, 64),    # ring buffer window
    (1, 2, 2, 32, 128, 0, 0, 128),       # first token
])
def test_decode_attention_sweep(dtype, B, H, Hkv, D, T, pos, window, bt, rng):
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    ck = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), dtype)
    cv = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), dtype)
    got = decode_attention(q, ck, cv, pos=jnp.int32(pos), window=window,
                           block_t=bt, interpret=True)
    want = ref_decode_attention(q, ck, cv, pos=pos, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,S,nh,P,G,N,Q", [
    (2, 128, 4, 32, 1, 16, 32),
    (1, 256, 8, 64, 2, 32, 64),
    (2, 64, 2, 16, 1, 8, 64),            # single chunk
    (1, 96, 4, 32, 4, 16, 32),           # groups == heads/1
])
def test_ssd_sweep(dtype, B, S, nh, P, G, N, Q, rng):
    x = jnp.asarray(rng.standard_normal((B, S, nh, P)) * 0.5, dtype)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, nh)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, (nh,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, dtype)
    Cm = jnp.asarray(rng.standard_normal((B, S, G, N)) * 0.3, dtype)
    y, st = ssd(x, dt, A, Bm, Cm, Q, True)
    yw, stw = ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw), atol=2e-4,
                               rtol=2e-4)


def test_ssd_chunked_xla_matches_ref(rng):
    """The XLA-path chunked formulation == naive recurrence (same math the
    kernel tiles)."""
    from repro.models.ssm import ssd_chunked
    x = jnp.asarray(rng.standard_normal((2, 128, 4, 32)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (2, 128, 4)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, (4,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((2, 128, 1, 16)) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((2, 128, 1, 16)) * 0.3, jnp.float32)
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_ssd_grads_finite(rng):
    x = jnp.asarray(rng.standard_normal((1, 64, 2, 16)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (1, 64, 2)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (2,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((1, 64, 1, 8)) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((1, 64, 1, 8)) * 0.3, jnp.float32)
    g = jax.grad(lambda x_: ssd(x_, dt, A, Bm, Cm, 32, True)[0].sum())(x)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("crop", [(0, 0, 32, 32), (8, 16, 32, 32),
                                  (1, 1, 30, 30)])
def test_fused_preprocess_sweep(crop, rng):
    imgs = jnp.asarray(rng.integers(0, 255, (3, 64, 64, 3)), jnp.uint8)
    mean, std = (0.48, 0.45, 0.41), (0.23, 0.22, 0.23)
    got = fused_preprocess(imgs, crop, mean, std, True)
    want = ref_preprocess(imgs, crop, mean, std)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert got.dtype == jnp.float32


def test_xla_blockwise_attention_matches_ref(rng):
    """The XLA train path (masked blocks) and the pair-scan variant both
    match the oracle — the §Perf optimization is a pure refactor."""
    from repro.models.attention import blockwise_attention
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), jnp.float32)
    want = ref_attention(q, k, v, causal=True, scale=0.25)
    got_masked = blockwise_attention(q, k, v, scale=0.25, causal=True,
                                     q_block=64, kv_block=64)
    got_pairs = blockwise_attention(q, k, v, scale=0.25, causal=True,
                                    q_block=64, kv_block=64, pairs=True)
    np.testing.assert_allclose(np.asarray(got_masked), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_pairs), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pairs", [False, True])
def test_xla_blockwise_attention_grads_match_ref_without_max_vjp(pairs, rng):
    """Gradients of the XLA train path equal the oracle's, and the backward
    holds no equality test against the score max: that reduce_max VJP divides
    by the count of maxima, which is 0 (so NaN) when a remat backward
    recomputes the scores with different rounding, as it does on the TPU."""
    from repro.models.attention import blockwise_attention
    B, S, H, Hkv, D, blk = 1, 64, 4, 2, 16, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)

    def f(q_, k_, v_):
        out = blockwise_attention(q_, k_, v_, scale=0.25, window=24,
                                  q_block=blk, kv_block=blk, pairs=pairs)
        return (out ** 2).sum()

    def f_ref(q_, k_, v_):
        out = ref_attention(q_, k_, v_, causal=True, window=24, scale=0.25)
        return (out ** 2).sum()

    grad = jax.grad(f, argnums=(0, 1, 2))
    for got, want in zip(grad(q, k, v),
                         jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # the scores' equality mask would print as ``bool[..., blk, blk] = eq``
    text = str(jax.make_jaxpr(grad)(q, k, v))
    assert not re.search(rf"bool\[[0-9,]*{blk},{blk}\] = eq ", text)
