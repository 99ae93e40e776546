"""Single-token decode attention kernel (TPU Pallas).

One query token per (batch, head) attends over a long KV cache.  Grid
(B, Hkv, nT) with the cache-block axis innermost: each program takes the G
query heads that share one KV head as a (G, D) tile, and streams that head's
cache blocks HBM->VMEM while the (G, D) accumulator + (G, 1) softmax stats
stay in VMEM scratch — flash-decoding restructured for the TPU's sequential
grid iteration (no cross-split reduction pass needed).  The cache
(B, T, Hkv, D) is viewed as (B, T, Hkv*D), so a cache block is one head's
(block_t, D) lane slice; on the chip D is a multiple of 128.

The current position arrives as a (1, 1) scalar in SMEM; blocks entirely
beyond ``pos`` are skipped with ``pl.when`` — at 500k cache and pos=1000
that's 99.8% of the streaming skipped, which a masked XLA einsum cannot do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_t: int, n_t: int, window: int):
    ti = pl.program_id(2)
    pos = pos_ref[0, 0]

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # ring-buffer caches (window) hold at most min(pos+1, T) valid entries
    limit = jnp.minimum(pos + 1, jnp.int32(n_t * block_t)) if window else pos + 1

    @pl.when(ti * block_t < limit)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                   # (G, D)
        k = k_ref[...].astype(jnp.float32)                   # (bt, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bt)
        s = s * (1.0 / (q.shape[-1] ** 0.5))
        idx = ti * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < limit, s, NEG_INF)
        m_prev = m_ref[...]                                  # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ti == n_t - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(q, cache_k, cache_v, *, pos, window: int = 0,
                         block_t: int = 512, interpret: bool = False):
    """q (B,H,D); caches (B,T,Hkv,D); pos () int32 -> out (B,H,D)."""
    B, H, D = q.shape
    T, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = H // Hkv
    block_t = min(block_t, T)
    assert T % block_t == 0, (T, block_t)
    n_t = T // block_t
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1, 1)
    q_spec = pl.BlockSpec((None, None, G, D), lambda b, g, ti: (b, g, 0, 0))
    kv_spec = pl.BlockSpec((None, block_t, D), lambda b, g, ti: (b, ti, g))

    kernel = functools.partial(_kernel, block_t=block_t, n_t=n_t, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_t),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, kv_spec,
                  kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),         # acc
            pltpu.VMEM((G, 1), jnp.float32),         # m (running max)
            pltpu.VMEM((G, 1), jnp.float32),         # l (running sum)
        ],
        interpret=interpret,
    )(pos_arr, q.reshape(B, Hkv, G, D), cache_k.reshape(B, T, Hkv * D),
      cache_v.reshape(B, T, Hkv * D))
    return out.reshape(B, H, D)
