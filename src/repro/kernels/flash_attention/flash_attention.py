"""Flash attention forward kernel (TPU Pallas).

Tiling: grid (B, H, nQ, nK), K-blocks innermost so each core streams KV
blocks through VMEM while the (block_q, D) accumulator + (block_q, 1) softmax
stats live in VMEM scratch across the nK steps.  GQA is handled in the
BlockSpec index maps (kv head = h // group_size), so no KV replication ever
touches HBM.  Causal/sliding-window blocks that are fully masked are skipped
with ``pl.when`` (the roofline win vs the masked XLA path).

Layout: (B, S, H, D) is viewed as (B, S, H*D) (a free reshape), and a block
is one head's (block, D) lane slice.  The TPU needs the last two block dims
divisible by (8, 128) or equal to the array's, so on the chip D is a
multiple of 128.

Block sizes default to (128, 512): MXU-aligned (multiples of 128 on the
contracted and lane dims) and sized so  q(128xD) + k,v(512xD) + acc fit in
~2 MB of VMEM at D=256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: int, block_q: int,
               block_k: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # skip blocks strictly above the causal diagonal / beyond the window
    def need_block():
        ok = True
        if causal:
            ok = jnp.logical_and(ok, k_start <= q_start + block_q - 1)
        if window:
            ok = jnp.logical_and(ok, k_start + block_k - 1 >= q_start - window + 1)
        return ok

    @pl.when(need_block())
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, D)
        k = k_ref[...].astype(jnp.float32)                 # (bk, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, q_pos >= k_pos)
        if window:
            ok = jnp.logical_and(ok, q_pos - k_pos < window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None, block_q: int = 128,
                        block_k: int = 512, interpret: bool = False):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_q, n_k = S // block_q, T // block_k
    grid = (B, H, n_q, n_k)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, n_k=n_k)
    q_spec = pl.BlockSpec((None, block_q, D), lambda b, h, qi, ki: (b, qi, h))
    kv_spec = pl.BlockSpec((None, block_k, D),
                           lambda b, h, qi, ki: (b, ki, h // G))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running sum)
        ],
        interpret=interpret,
    )(q.reshape(B, S, H * D), k.reshape(B, T, Hkv * D),
      v.reshape(B, T, Hkv * D))
    return out.reshape(B, S, H, D)
