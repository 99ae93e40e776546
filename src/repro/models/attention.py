"""Attention: GQA/MQA (RoPE, causal, sliding-window) and DeepSeek-style MLA.

Three execution paths:

* ``xla`` — blockwise online-softmax attention expressed in pure lax ops
  (scan over query blocks, scan over KV blocks with running (m, l, acc)).
  Never materializes the S×S score matrix, so prefill_32k fits.  Causal
  masking is applied per block; blocks entirely above the diagonal are
  still computed then masked (the cost shows up in HLO FLOPs — see
  EXPERIMENTS.md §Perf where the pair-scan variant removes it).
* ``xla_pairs`` — beyond-paper optimized causal path: a scan over only the
  lower-triangular (q-block, kv-block) pairs, halving attention FLOPs.
* ``pallas`` / ``pallas_interpret`` — the flash-attention TPU kernel
  (kernels/flash_attention), used on real TPUs / in tests respectively.

Decode is single-token: direct einsum over the cache (scores (B,H,T) is
small even at T=512k), with cache update via dynamic_update_slice; local
(sliding-window) layers keep a ring-buffer cache of size ``window``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import apply_rope
from .param import ParamSpec

NEG_INF = -1e30


# ------------------------------------------------------------------ specs
def gqa_specs(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, ParamSpec]:
    ax = (None,) * len(stack)
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec(stack + (d, H, hd), ax + ("fsdp", "model", None),
                        dtype=cfg.dtype, fan_in=d),
        "wk": ParamSpec(stack + (d, Hkv, hd), ax + ("fsdp", "model", None),
                        dtype=cfg.dtype, fan_in=d),
        "wv": ParamSpec(stack + (d, Hkv, hd), ax + ("fsdp", "model", None),
                        dtype=cfg.dtype, fan_in=d),
        "wo": ParamSpec(stack + (H, hd, d), ax + ("model", None, "fsdp"),
                        dtype=cfg.dtype, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec(stack + (H, hd), ax + ("model", None), init="zeros",
                                dtype=cfg.dtype)
        specs["bk"] = ParamSpec(stack + (Hkv, hd), ax + ("model", None), init="zeros",
                                dtype=cfg.dtype)
        specs["bv"] = ParamSpec(stack + (Hkv, hd), ax + ("model", None), init="zeros",
                                dtype=cfg.dtype)
    return specs


def mla_specs(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, ParamSpec]:
    ax = (None,) * len(stack)
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.nope_head_dim
    return {
        "w_dq": ParamSpec(stack + (d, m.q_lora_rank), ax + ("fsdp", None), dtype=cfg.dtype),
        "q_norm": ParamSpec(stack + (m.q_lora_rank,), ax + (None,), init="ones",
                            dtype="float32"),
        "w_uq": ParamSpec(stack + (m.q_lora_rank, H, qk + m.rope_head_dim),
                          ax + (None, "model", None), dtype=cfg.dtype,
                          fan_in=m.q_lora_rank),
        "w_dkv": ParamSpec(stack + (d, m.kv_lora_rank), ax + ("fsdp", None), dtype=cfg.dtype),
        "kv_norm": ParamSpec(stack + (m.kv_lora_rank,), ax + (None,), init="ones",
                             dtype="float32"),
        "w_uk": ParamSpec(stack + (m.kv_lora_rank, H, qk),
                          ax + (None, "model", None), dtype=cfg.dtype,
                          fan_in=m.kv_lora_rank),
        "w_uv": ParamSpec(stack + (m.kv_lora_rank, H, m.v_head_dim),
                          ax + (None, "model", None), dtype=cfg.dtype,
                          fan_in=m.kv_lora_rank),
        "w_kr": ParamSpec(stack + (d, m.rope_head_dim), ax + ("fsdp", None), dtype=cfg.dtype),
        "wo": ParamSpec(stack + (H, m.v_head_dim, d), ax + ("model", None, "fsdp"),
                        dtype=cfg.dtype, fan_in=H * m.v_head_dim),
    }


# ------------------------------------------------------- qkv projections
def gqa_qkv(params, x, positions, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# -------------------------------------------------- blockwise XLA attention
def _block_mask(q_pos, k_pos, window: int):
    """(qc, kc) additive mask for causal (+ optional sliding window)."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = diff >= 0
    if window:
        ok = jnp.logical_and(ok, diff < window)
    return jnp.where(ok, 0.0, NEG_INF)


def _online_block(acc, m, l, q, k, v, mask, scale):
    """One (q-block × kv-block) online-softmax update. fp32 stats.

    The running max only shifts exponents and cancels in acc / l, so it
    carries no gradient.  Differentiating it would also be unsafe: the
    reduce_max VJP divides by the count of entries equal to the max, which
    is 0 when the backward pass recomputes ``s`` in a different fusion and
    rounding (full remat on the TPU), and so turns every q/k gradient NaN.
    """
    s = jnp.einsum("bqgnd,bkgd->bgnqk", q, k).astype(jnp.float32) * scale
    s = s + mask[None, None, None, :, :]
    m_new = jax.lax.stop_gradient(jnp.maximum(m, s.max(axis=-1)))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bgnqk,bkgd->bgnqd", p.astype(v.dtype), v).astype(jnp.float32)
    return acc_new, m_new, l_new


def blockwise_attention(q, k, v, *, scale: float, causal: bool = True,
                        window: int = 0, q_block: int = 512,
                        kv_block: int = 512, pairs: bool = False,
                        q_offset=0) -> jax.Array:
    """q (B,S,H,D), k/v (B,T,Hkv,D) -> (B,S,H,D); never materializes SxT.

    ``pairs=True`` scans only lower-triangular block pairs (causal FLOPs
    halved); requires S == T and q_offset == 0.
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    if S % q_block or T % kv_block:
        # pad to block multiples; padded keys sit at positions >= T so the
        # causal mask hides them, padded query rows are sliced off below
        S_pad = -(-S // q_block) * q_block
        T_pad = -(-T // kv_block) * kv_block
        q_p = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        k_p = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v_p = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        out = blockwise_attention(q_p, k_p, v_p, scale=scale, causal=True,
                                  window=window, q_block=q_block,
                                  kv_block=kv_block, pairs=pairs,
                                  q_offset=q_offset)
        return out[:, :S]
    nq, nk = S // q_block, T // kv_block
    qg = q.reshape(B, nq, q_block, Hkv, G, D)
    kg = k.reshape(B, nk, kv_block, Hkv, D)
    vg = v.reshape(B, nk, kv_block, Hkv, D)
    q_pos_base = jnp.arange(S) + q_offset
    k_pos = jnp.arange(T)

    if pairs and causal and S == T and q_block == kv_block:
        return _pairs_attention(qg, kg, vg, scale, window, q_block, nq, B, Hkv,
                                G, D, S, H)

    def per_qblock(qi, qb):
        q_pos = q_pos_base[qi * q_block:(qi + 1) * q_block] if False else \
            jax.lax.dynamic_slice_in_dim(q_pos_base, qi * q_block, q_block)

        def inner(carry, inputs):
            acc, m, l = carry
            kb, vb, ki = inputs
            kp = jax.lax.dynamic_slice_in_dim(k_pos, ki * kv_block, kv_block)
            mask = _block_mask(q_pos, kp, window) if (causal or window) else \
                jnp.zeros((q_block, kv_block))
            acc, m, l = _online_block(acc, m, l, qb, kb, vb, mask, scale)
            return (acc, m, l), None

        acc0 = jnp.zeros((B, Hkv, G, q_block, D), jnp.float32)
        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            inner, (acc0, m0, l0),
            (jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0), jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (B, Hkv, G, q_block, D)

    outs = jax.lax.map(lambda args: per_qblock(*args),
                       (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    # (nq, B, Hkv, G, q_block, D) -> (B, S, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq, Hkv, G, q_block, D)
    out = jnp.moveaxis(out, (1, 4), (1, 2)).reshape(B, S, Hkv * G, D)
    return out.astype(q.dtype)


def _pairs_attention(qg, kg, vg, scale, window, blk, nb, B, Hkv, G, D, S, H):
    """Beyond-paper causal path: scan lower-triangular block pairs only.

    Pairs are ordered row-major (qi ascending, ki ascending within qi) so the
    online-softmax state for each q block is finalized before the next row
    starts; states for ALL q blocks are carried (they live in the output
    accumulator anyway).
    """
    pairs = np.array([(qi, ki) for qi in range(nb) for ki in range(qi + 1)],
                     dtype=np.int32)
    pos = jnp.arange(S)

    def body(carry, pair):
        acc, m, l = carry
        qi, ki = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qg, qi, 1, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kg, ki, 1, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vg, ki, 1, keepdims=False)
        qp = jax.lax.dynamic_slice_in_dim(pos, qi * blk, blk)
        kp = jax.lax.dynamic_slice_in_dim(pos, ki * blk, blk)
        mask = _block_mask(qp, kp, window)
        acc_i = jax.lax.dynamic_index_in_dim(acc, qi, 1, keepdims=False)
        m_i = jax.lax.dynamic_index_in_dim(m, qi, 1, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, qi, 1, keepdims=False)
        acc_i, m_i, l_i = _online_block(acc_i, m_i, l_i, qb, kb, vb, mask, scale)
        acc = jax.lax.dynamic_update_index_in_dim(acc, acc_i, qi, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_i, qi, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_i, qi, 1)
        return (acc, m, l), None

    acc0 = jnp.zeros((B, nb, Hkv, G, blk, D), jnp.float32)
    m0 = jnp.full((B, nb, Hkv, G, blk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nb, Hkv, G, blk), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.asarray(pairs))
    out = acc / jnp.maximum(l[..., None], 1e-30)       # (B, nb, Hkv, G, blk, D)
    out = jnp.moveaxis(out, 4, 2).reshape(B, S, Hkv * G, D)
    return out.astype(qg.dtype)


# ------------------------------------------------------------ public paths
def gqa_attend(q, k, v, cfg, *, window: int = 0, impl: str = "xla",
               q_offset=0) -> jax.Array:
    scale = 1.0 / np.sqrt(q.shape[-1])
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(
            q, k, v, causal=True, window=window, scale=scale,
            interpret=(impl == "pallas_interpret"))
    return blockwise_attention(q, k, v, scale=scale, causal=True, window=window,
                               pairs=(impl == "xla_pairs"), q_offset=q_offset)


@jax.named_scope("attention")
def gqa_train(params, x, positions, cfg, *, window: int = 0,
              impl: str = "xla") -> jax.Array:
    q, k, v = gqa_qkv(params, x, positions, cfg)
    out = gqa_attend(q, k, v, cfg, window=window, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


@jax.named_scope("attention")
def gqa_prefill(params, x, positions, cfg, *, window: int = 0,
                impl: str = "xla"):
    """Forward + return the KV cache this segment produces."""
    q, k, v = gqa_qkv(params, x, positions, cfg)
    out = gqa_attend(q, k, v, cfg, window=window, impl=impl)
    if window:
        k, v = k[:, -window:], v[:, -window:]
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), (k, v)


def _write_position(cache, new, slot, layer):
    """Write one position ``new`` (B,1,...) into ``cache`` at ``slot`` along
    its time axis: a (B,T,...) cache, or with ``layer`` that layer of a
    stacked (L,B,T,...) cache, updated in place (no layer is sliced out)."""
    new = new.astype(cache.dtype)
    if layer is None:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, slot, axis=1)
    zero = jnp.zeros((), jnp.int32)
    start = (layer, zero, slot) + (zero,) * (new.ndim - 2)
    return jax.lax.dynamic_update_slice(cache, new[None], start)


def _layer(cache, layer):
    """The (B,T,...) cache that attention reads: ``cache`` itself, or layer
    ``layer`` of a stacked one (a read: the stack is not written)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


@jax.named_scope("attention")
def gqa_decode(params, x, cache_k, cache_v, pos, cfg, *, window: int = 0,
               impl: str = "xla", layer=None):
    """One-token decode. x (B,1,d); pos () int32.

    The caches are (B,T,Hkv,D), or with ``layer`` (an int32 scalar) the
    stacked (L,B,T,Hkv,D) caches of every layer.  Exactly one position is
    written, at (``layer``,) b, slot; the layer's (B,T,Hkv,D) slice is only
    read, for the two attention products.  A caller that donates the stacked
    caches (``Model.decode_step`` under ``Server``) gets them back updated
    in place.  Local layers use a ring buffer of size ``window``
    (slot = pos % window).  Returns (out, cache_k, cache_v) in the form
    given.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    slot = (pos % window) if window else pos
    with jax.named_scope("kv_write"):
        cache_k = _write_position(cache_k, k, slot, layer)
        cache_v = _write_position(cache_v, v, slot, layer)
    ck, cv = _layer(cache_k, layer), _layer(cache_v, layer)
    T = ck.shape[1]
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(
            q[:, 0], ck, cv, pos=pos, window=window,
            interpret=(impl == "pallas_interpret"))[:, None]
    else:
        B, _, H, D = q.shape
        Hkv = ck.shape[2]
        G = H // Hkv
        qg = q.reshape(B, Hkv, G, D)
        s = jnp.einsum("bgnd,btgd->bgnt", qg, ck).astype(jnp.float32)
        s = s / np.sqrt(D)
        idx = jnp.arange(T)
        if window:
            valid = jnp.logical_and(idx != slot, idx < jnp.minimum(pos, window))
            valid = jnp.logical_or(valid, idx == slot)
        else:
            valid = idx <= pos
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bgnt,btgd->bgnd", p.astype(cv.dtype), cv)
        out = out.reshape(B, 1, H, D)
    proj = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
    return proj, cache_k, cache_v


# ------------------------------------------------------------------- MLA
def _mla_rms(scale, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def mla_project_q(params, x, positions, cfg):
    m = cfg.mla
    cq = _mla_rms(params["q_norm"], x @ params["w_dq"])
    q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    q_nope = q[..., : m.nope_head_dim]
    q_rope = apply_rope(q[..., m.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_latents(params, x, positions, cfg):
    m = cfg.mla
    c_kv = _mla_rms(params["kv_norm"], x @ params["w_dkv"])     # (B,S,r)
    k_rope = (x @ params["w_kr"])[:, :, None, :]                # (B,S,1,rd)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


@jax.named_scope("attention")
def mla_train(params, x, positions, cfg, *, impl: str = "xla") -> jax.Array:
    """Training path: expand K/V from latents, run standard attention."""
    m = cfg.mla
    q_nope, q_rope = mla_project_q(params, x, positions, cfg)
    c_kv, k_rope = mla_latents(params, x, positions, cfg)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uv"])
    H = cfg.num_heads
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_rope.shape[:2] + (H, m.rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    # pad V up to the QK head dim so one attention call serves both
    scale = 1.0 / np.sqrt(m.nope_head_dim + m.rope_head_dim)
    out = blockwise_attention(q, k, v_pad := jnp.pad(
        v, ((0, 0), (0, 0), (0, 0), (0, q.shape[-1] - v.shape[-1]))),
        scale=scale, causal=True, pairs=(impl == "xla_pairs"))
    out = out[..., : m.v_head_dim]
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


@jax.named_scope("attention")
def mla_prefill(params, x, positions, cfg, *, impl: str = "xla"):
    out = mla_train(params, x, positions, cfg, impl=impl)
    c_kv, k_rope = mla_latents(params, x, positions, cfg)
    return out, (c_kv, k_rope)


@jax.named_scope("attention")
def mla_decode(params, x, cache_ckv, cache_kr, pos, cfg, *, layer=None):
    """Absorbed single-token MLA decode: attend in the 512-d latent space.

    Cache holds (c_kv, k_rope) only — the MLA memory win: r + rd floats per
    token instead of 2·H·D.  The caches are (B,T,r) and (B,T,rd), or with
    ``layer`` the stacked (L,B,T,...) caches, written at one position and
    otherwise only read, as in ``gqa_decode``.
    """
    m = cfg.mla
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = mla_project_q(params, x, positions, cfg)   # (B,1,H,*)
    c_kv, k_rope = mla_latents(params, x, positions, cfg)       # (B,1,r),(B,1,rd)
    with jax.named_scope("kv_write"):
        cache_ckv = _write_position(cache_ckv, c_kv, pos, layer)
        cache_kr = _write_position(cache_kr, k_rope, pos, layer)
    ckv, kr = _layer(cache_ckv, layer), _layer(cache_kr, layer)
    # absorb W_uk into q:  q_abs (B,H,r)
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], params["w_uk"])
    s = jnp.einsum("bhr,btr->bht", q_abs, ckv).astype(jnp.float32)
    s = s + jnp.einsum("bhk,btk->bht", q_rope[:, 0], kr).astype(jnp.float32)
    s = s / np.sqrt(m.nope_head_dim + m.rope_head_dim)
    T = ckv.shape[1]
    s = jnp.where((jnp.arange(T) <= pos)[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", p.astype(ckv.dtype), ckv)
    out = jnp.einsum("bhr,rhk->bhk", ctx, params["w_uv"])        # (B,H,vd)
    proj = jnp.einsum("bhk,hkd->bd", out.astype(x.dtype), params["wo"])[:, None]
    return proj, cache_ckv, cache_kr
