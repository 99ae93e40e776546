"""Pure-jnp oracle for flash attention (causal + sliding window + GQA)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def ref_attention(q, k, v, *, causal: bool = True, window: int = 0,
                  scale: float | None = None) -> jnp.ndarray:
    """q (B,S,H,D), k/v (B,T,Hkv,D) -> (B,S,H,D); materializes SxT (oracle)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bsgnd,btgd->bgnst", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok = ok & (qi >= ki)
    if window:
        ok = ok & (qi - ki < window)
    s = jnp.where(ok[None, None, None], s, -1e30)
    # the max cancels in p; keeping it out of the VJP (which the flash
    # kernel's backward takes through this function) avoids a 0/0
    p = jnp.exp(s - jax.lax.stop_gradient(s.max(axis=-1, keepdims=True)))
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgnst,btgd->bsgnd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)
