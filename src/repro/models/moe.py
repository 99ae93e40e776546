"""Mixture-of-Experts FFN with sort-based, capacity-bounded dispatch.

TPU-native adaptation (DESIGN.md §2): instead of a dense (tokens × experts ×
capacity) one-hot dispatch einsum — whose memory explodes at DeepSeek scale
(256 experts) — tokens are *sorted by expert id* and scattered into a compact
(E, C, d) buffer, computed with one stacked einsum per FFN matrix (MXU
friendly), and combined back with top-k router weights.  All shapes static,
fully differentiable (sorting indices are constants of the backward pass).

Expert weights carry the "expert"→model logical axis, so pjit shards experts
across the `model` mesh axis (EP); GSPMD inserts the token all-to-alls at the
scatter/gather boundaries.

Routers: `softmax` (standard, granite) and `sigmoid` (DeepSeek-V3 style:
sigmoid affinities, top-k, weights renormalized over the selected set).
Aux load-balance loss follows Switch/DeepSeek conventions.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .param import ParamSpec


def moe_specs(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, ParamSpec]:
    ax = (None,) * len(stack)
    m = cfg.moe
    d, E, f = cfg.d_model, m.num_experts, m.d_expert
    specs = {
        "router": ParamSpec(stack + (d, E), ax + (None, None), dtype="float32"),
        "wi": ParamSpec(stack + (E, d, f), ax + ("expert", "fsdp", None), dtype=cfg.dtype),
        "wg": ParamSpec(stack + (E, d, f), ax + ("expert", "fsdp", None), dtype=cfg.dtype),
        "wo": ParamSpec(stack + (E, f, d), ax + ("expert", None, "fsdp"), dtype=cfg.dtype),
    }
    if m.num_shared:
        fs = f * m.num_shared
        specs["shared_wi"] = ParamSpec(stack + (d, fs), ax + ("fsdp", "model"),
                                       dtype=cfg.dtype)
        specs["shared_wg"] = ParamSpec(stack + (d, fs), ax + ("fsdp", "model"),
                                       dtype=cfg.dtype)
        specs["shared_wo"] = ParamSpec(stack + (fs, d), ax + ("model", "fsdp"),
                                       dtype=cfg.dtype)
    return specs


def _route(params, x2d: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (top-k expert ids (T,k), weights (T,k) in x dtype, aux loss)."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    if m.router == "sigmoid":                     # DeepSeek-V3
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, m.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balance: E * sum_e mean_tokens(frac_e) * mean(prob_e)
    E = m.num_experts
    onehot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    frac = onehot.mean(axis=0)
    aux = E * jnp.sum(frac * probs.mean(axis=0))
    return idx, w, aux


@jax.named_scope("moe")
def moe_apply(params, x: jax.Array, cfg,
              shard=lambda x, axes=None: x) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    ``shard`` pins the dispatch intermediates: the expert-sorted token table
    is sharded along the sorted (expert-major) axis onto the `model` mesh
    axis, so the scatter into the (E, C, d) buffer is the EP all-to-all and
    nothing token-sized is ever replicated.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2d = shard(x.reshape(T, d), ("batch", None))
    idx, w, aux = _route(params, x2d, cfg)

    k, E = m.top_k, m.num_experts
    cap = int((T * k / E) * m.capacity_factor)
    cap = max(8, -(-cap // 8) * 8)                     # round up to 8

    flat_e = idx.reshape(T * k)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    flat_w = w.reshape(T * k)
    order = jnp.argsort(flat_e)                        # stable
    se = shard(flat_e[order], ("expert",))
    st = shard(flat_tok[order], ("expert",))
    sw = shard(flat_w[order], ("expert",))
    # position of each assignment within its expert's queue
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = shard(jnp.arange(T * k) - starts[se], ("expert",))
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    gathered = shard(x2d[st] * keep[:, None].astype(x2d.dtype),
                     ("expert", None))
    buf = shard(jnp.zeros((E, cap, d), x2d.dtype).at[se, pos_c].add(gathered),
                ("expert", None, None))

    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
    h = shard(jax.nn.silu(g) * h, ("expert", None, None))
    out_buf = shard(jnp.einsum("ecf,efd->ecd", h, params["wo"]),
                    ("expert", None, None))

    y = out_buf[se, pos_c] * (keep.astype(x2d.dtype) * sw.astype(x2d.dtype))[:, None]
    y = shard(y, ("expert", None))
    out = shard(jnp.zeros((T, d), x2d.dtype).at[st].add(y), ("batch", None))

    if m.num_shared:
        sh = jnp.einsum("td,df->tf", x2d, params["shared_wi"])
        sg = jnp.einsum("td,df->tf", x2d, params["shared_wg"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(sg) * sh,
                               params["shared_wo"])
    return out.reshape(B, S, d), aux
