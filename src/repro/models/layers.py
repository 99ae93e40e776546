"""Shared neural layers: norms, rotary embeddings, GLU MLPs, embedding/head.

Pure-functional JAX: every layer is ``fn(params, x, ...)`` with params built
from :class:`ParamSpec` trees.  Activation sharding constraints are applied at
block boundaries by the caller (model.py) — layers stay mesh-agnostic.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .param import ParamSpec


# ----------------------------------------------------------------- norms
def rmsnorm_spec(dim: int) -> ParamSpec:
    return ParamSpec((dim,), (None,), init="ones", dtype="float32")


@jax.named_scope("norm")
def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------ rope
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_frequencies(x.shape[-1], theta)                 # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- mlp
def mlp_specs(d_model: int, d_ff: int, variant: str, dtype: str,
              stack: Tuple[int, ...] = ()) -> dict:
    ax = (None,) * len(stack)
    gated = variant.endswith("_glu")
    specs = {
        "wi": ParamSpec(stack + (d_model, d_ff), ax + ("fsdp", "model"), dtype=dtype),
        "wo": ParamSpec(stack + (d_ff, d_model), ax + ("model", "fsdp"), dtype=dtype),
    }
    if gated:
        specs["wg"] = ParamSpec(stack + (d_model, d_ff), ax + ("fsdp", "model"),
                                dtype=dtype)
    return specs


@jax.named_scope("mlp")
def mlp(params: dict, x: jax.Array, variant: str) -> jax.Array:
    h = x @ params["wi"]
    if variant == "silu_glu":
        h = jax.nn.silu(x @ params["wg"]) * h
    elif variant == "gelu_glu":
        h = jax.nn.gelu(x @ params["wg"], approximate=True) * h
    elif variant == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    else:
        raise ValueError(variant)
    return h @ params["wo"]


# ------------------------------------------------------------- embeddings
def embedding_spec(vocab: int, d_model: int, dtype: str) -> ParamSpec:
    return ParamSpec((vocab, d_model), ("vocab", "fsdp"), init="normal",
                     scale=1.0, dtype=dtype)


@jax.named_scope("embed")
def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed_logits(table_or_head: jax.Array, h: jax.Array,
                   transpose: bool) -> jax.Array:
    """h (..., d) x (V, d)ᵀ or (d, V) -> logits (..., V), fp32 for stability."""
    w = table_or_head.astype(jnp.float32)
    hf = h.astype(jnp.float32)
    return hf @ (w.T if transpose else w)


# ------------------------------------------------------------------- loss
@jax.named_scope("head")
def softmax_cross_entropy(logits: jax.Array, targets: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean CE over possibly vocab-sharded logits (GSPMD inserts the
    cross-shard max/sum reductions)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------- remat
def remat_policy(name: str):
    if name == "none":
        return None
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots_no_batch":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    raise ValueError(f"unknown remat policy {name!r}")


def maybe_remat(fn, policy_name: str):
    policy = remat_policy(policy_name)
    if policy is None and policy_name == "none":
        return fn
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)
