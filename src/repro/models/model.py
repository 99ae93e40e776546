"""Model assembly: one `Model` API over four architecture families.

    dense   - starcoder2 / qwen2 / gemma / gemma3 / musicgen / phi3v backbones
    moe     - deepseek-v3 (MLA + 1 shared + 256 routed), granite (GQA + 32e)
    ssm     - mamba2 (attention-free SSD)
    hybrid  - zamba2 (mamba2 backbone + one SHARED GQA block every N layers)

Design notes (compile-scale):
* layers are stacked and iterated with `lax.scan` so the HLO stays one
  block body regardless of depth (80-layer qwen2 compiles like 1 layer);
* heterogeneous patterns (gemma3 5 local : 1 global) scan over *periods*
  with a static inner loop, remainder layers in a small tail scan;
* zamba2's shared attention block is closed over (not scanned), so its
  parameters are physically shared across all invocations;
* activations get logical sharding constraints via ``self.shard`` at block
  boundaries (MaxText-style), which the launcher binds to the mesh.

API:
    m = build_model(cfg)
    specs  = m.param_specs()                  # ParamSpec pytree
    params = m.init(key)                      # real arrays (smoke scale)
    loss, metrics = m.loss_fn(params, batch)  # train forward
    logits, cache = m.prefill(params, batch)
    logits, cache = m.decode_step(params, cache, tokens, pos)
    cache_sp = m.cache_specs(batch, max_len)  # ParamSpec pytree for dry-run
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import (embed, maybe_remat, mlp, mlp_specs, rmsnorm,
                     softmax_cross_entropy)
from .param import ParamSpec, abstract, materialize


def _ln(d: int, stack: Tuple[int, ...] = ()) -> ParamSpec:
    return ParamSpec(stack + (d,), (None,) * len(stack) + (None,), init="ones",
                     dtype="float32")


Identity = lambda x, axes=None: x


class Model:
    def __init__(self, cfg: ModelConfig, shard_fn: Callable = Identity,
                 attn_impl: str = "xla") -> None:
        self.cfg = cfg
        self.shard = shard_fn
        self.attn_impl = attn_impl

    # ------------------------------------------------------------ param specs
    def _attn_specs(self, stack):
        if self.cfg.attention == "mla":
            return attn.mla_specs(self.cfg, stack)
        return attn.gqa_specs(self.cfg, stack)

    def _dense_block_specs(self, stack):
        cfg = self.cfg
        return {
            "ln1": _ln(cfg.d_model, stack),
            "attn": self._attn_specs(stack),
            "ln2": _ln(cfg.d_model, stack),
            "mlp": mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp, cfg.dtype, stack),
        }

    def _moe_block_specs(self, stack):
        cfg = self.cfg
        return {
            "ln1": _ln(cfg.d_model, stack),
            "attn": self._attn_specs(stack),
            "ln2": _ln(cfg.d_model, stack),
            "moe": moe_lib.moe_specs(cfg, stack),
        }

    def _ssm_block_specs(self, stack):
        return {"ln": _ln(self.cfg.d_model, stack),
                "ssm": ssm_lib.ssm_specs(self.cfg, stack)}

    def _shared_attn_specs(self):
        """zamba2 shared block: GQA + (optional) MLP, UNSTACKED."""
        cfg = self.cfg
        h = cfg.hybrid
        sub = cfg.with_(num_heads=h.shared_attn_heads,
                        num_kv_heads=h.shared_attn_kv_heads,
                        head_dim=cfg.d_model // h.shared_attn_heads)
        specs = {"ln1": _ln(cfg.d_model), "attn": attn.gqa_specs(sub)}
        if h.shared_attn_d_ff:
            specs["ln2"] = _ln(cfg.d_model)
            specs["mlp"] = mlp_specs(cfg.d_model, h.shared_attn_d_ff, cfg.mlp,
                                     cfg.dtype)
        return specs

    def param_specs(self):
        cfg = self.cfg
        specs: Dict[str, Any] = {}
        # ---- embeddings / modality frontends
        V = cfg.padded_vocab   # padded so the vocab axis always TP-shards
        if cfg.num_codebooks:          # musicgen: K codebook embeddings + heads
            specs["embed"] = ParamSpec((cfg.num_codebooks, V, cfg.d_model),
                                       (None, "vocab", "fsdp"),
                                       dtype=cfg.dtype, fan_in=cfg.d_model)
            specs["head"] = ParamSpec((cfg.d_model, cfg.num_codebooks, V),
                                      ("fsdp", None, "vocab"),
                                      dtype=cfg.dtype, fan_in=cfg.d_model)
        else:
            specs["embed"] = ParamSpec((V, cfg.d_model),
                                       ("vocab", "fsdp"), dtype=cfg.dtype,
                                       fan_in=cfg.d_model)
            if not cfg.tie_embeddings:
                specs["head"] = ParamSpec((cfg.d_model, V),
                                          ("fsdp", "vocab"), dtype=cfg.dtype)
        if cfg.num_image_tokens:       # phi3v: projector from frontend stub
            specs["img_proj"] = ParamSpec((1024, cfg.d_model), (None, "fsdp"),
                                          dtype=cfg.dtype)
        specs["final_ln"] = _ln(cfg.d_model)
        # ---- blocks per family
        if cfg.family in ("dense", "audio", "vlm"):
            if cfg.local_global_pattern:
                P = len(cfg.local_global_pattern)
                n_per, n_tail = divmod(cfg.num_layers, P)
                specs["periods"] = self._dense_block_specs((n_per, P))
                if n_tail:
                    specs["tail"] = self._dense_block_specs((n_tail,))
            else:
                specs["blocks"] = self._dense_block_specs((cfg.num_layers,))
        elif cfg.family == "moe":
            nd = cfg.moe.first_dense_layers
            if nd:
                specs["dense_blocks"] = self._dense_block_specs((nd,))
            specs["moe_blocks"] = self._moe_block_specs((cfg.num_layers - nd,))
            if cfg.mtp_depth:
                specs["mtp"] = {
                    "proj": ParamSpec((2 * cfg.d_model, cfg.d_model),
                                      ("fsdp", None), dtype=cfg.dtype),
                    "block": self._dense_block_specs(()),
                    "ln": _ln(cfg.d_model),
                }
        elif cfg.family == "ssm":
            specs["blocks"] = self._ssm_block_specs((cfg.num_layers,))
        elif cfg.family == "hybrid":
            P = cfg.hybrid.shared_attn_period
            n_per = cfg.num_layers // P
            specs["shared_attn"] = self._shared_attn_specs()
            specs["mamba"] = self._ssm_block_specs((n_per, P))
        else:
            raise ValueError(cfg.family)
        return specs

    def init(self, key: jax.Array, dtype_override: Optional[str] = None):
        return materialize(self.param_specs(), key, dtype_override)

    def abstract_params(self):
        return abstract(self.param_specs())

    # ------------------------------------------------------------- block fwd
    def _dense_block(self, p, h, positions, kind: str, aux):
        cfg = self.cfg
        window = cfg.sliding_window if kind == "L" else 0
        hn = rmsnorm(p["ln1"], h, cfg.norm_eps)
        if cfg.attention == "mla":
            a = attn.mla_train(p["attn"], hn, positions, cfg, impl=self.attn_impl)
        else:
            a = attn.gqa_train(p["attn"], hn, positions, cfg, window=window,
                               impl=self.attn_impl)
        h = h + a
        h = self.shard(h, ("batch", None, None))
        hn = rmsnorm(p["ln2"], h, cfg.norm_eps)
        if "moe" in p:
            out, aux_i = moe_lib.moe_apply(p["moe"], hn, cfg, shard=self.shard)
            aux = aux + aux_i
        else:
            out = mlp(p["mlp"], hn, cfg.mlp)
        h = h + out
        return self.shard(h, ("batch", None, None)), aux

    def _ssm_block(self, p, h):
        hn = rmsnorm(p["ln"], h, self.cfg.norm_eps)
        out = ssm_lib.mamba2_forward(p["ssm"], hn, self.cfg, impl=self.attn_impl
                                     if self.attn_impl.startswith("pallas")
                                     else "xla")
        return self.shard(h + out, ("batch", None, None))

    def _shared_attn_block(self, p, h, positions):
        cfg = self.cfg
        hb = cfg.hybrid
        sub = cfg.with_(num_heads=hb.shared_attn_heads,
                        num_kv_heads=hb.shared_attn_kv_heads,
                        head_dim=cfg.d_model // hb.shared_attn_heads)
        hn = rmsnorm(p["ln1"], h, cfg.norm_eps)
        h = h + attn.gqa_train(p["attn"], hn, positions, sub, impl=self.attn_impl)
        if "mlp" in p:
            hn = rmsnorm(p["ln2"], h, cfg.norm_eps)
            h = h + mlp(p["mlp"], hn, cfg.mlp)
        return self.shard(h, ("batch", None, None))

    # --------------------------------------------------------------- embed
    @jax.named_scope("embed")
    def _embed_tokens(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.num_codebooks:                           # (B, K, S)
            h = None
            for k in range(cfg.num_codebooks):
                e = embed(params["embed"][k], tokens[:, k])
                h = e if h is None else h + e
        else:
            h = embed(params["embed"], tokens)          # (B, S, d)
        h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)  # gemma-style scale
        if cfg.num_image_tokens and "image_embeds" in batch:
            img = batch["image_embeds"].astype(h.dtype) @ params["img_proj"]
            h = jnp.concatenate([img, h[:, cfg.num_image_tokens:]], axis=1)
        return self.shard(h, ("batch", None, None))

    @jax.named_scope("head")
    def _logits(self, params, h):
        cfg = self.cfg
        hf = h.astype(jnp.float32)
        if cfg.num_codebooks:
            logits = jnp.einsum("bsd,dkv->bskv", hf,
                                params["head"].astype(jnp.float32))
        elif cfg.tie_embeddings:
            logits = hf @ params["embed"].astype(jnp.float32).T
        else:
            logits = hf @ params["head"].astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad slots out of softmax
            valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(valid, logits, -1e30)
        return logits

    # -------------------------------------------------------------- backbone
    def backbone(self, params, h, positions):
        """Token embeddings -> final hidden states. Returns (h, aux_loss)."""
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "audio", "vlm"):
            if cfg.local_global_pattern:
                pat = cfg.local_global_pattern
                Pn = len(pat)

                def period_body(carry, p):
                    hh, aux = carry
                    for i, kind in enumerate(pat):
                        pi = jax.tree_util.tree_map(lambda x: x[i], p)
                        hh, aux = self._dense_block(pi, hh, positions, kind, aux)
                    return (hh, aux), None

                body = maybe_remat(period_body, cfg.remat)
                (h, aux), _ = jax.lax.scan(body, (h, aux0), params["periods"])
                if "tail" in params:
                    n_tail = cfg.num_layers % Pn

                    def tail_body(carry, p):
                        hh, aux = carry
                        hh, aux = self._dense_block(p, hh, positions,
                                                    pat[0], aux)
                        return (hh, aux), None

                    (h, aux), _ = jax.lax.scan(maybe_remat(tail_body, cfg.remat),
                                               (h, aux), params["tail"])
                return h, aux
            kind = "L" if cfg.sliding_window else "G"

            def body(carry, p):
                hh, aux = carry
                hh, aux = self._dense_block(p, hh, positions, kind, aux)
                return (hh, aux), None

            (h, aux), _ = jax.lax.scan(maybe_remat(body, cfg.remat), (h, aux0),
                                       params["blocks"])
            return h, aux

        if cfg.family == "moe":
            aux = aux0
            if "dense_blocks" in params:
                def dbody(carry, p):
                    hh, aux = carry
                    hh, aux = self._dense_block(p, hh, positions, "G", aux)
                    return (hh, aux), None
                (h, aux), _ = jax.lax.scan(maybe_remat(dbody, cfg.remat),
                                           (h, aux), params["dense_blocks"])

            def mbody(carry, p):
                hh, aux = carry
                hh, aux = self._dense_block(p, hh, positions, "G", aux)
                return (hh, aux), None

            (h, aux), _ = jax.lax.scan(maybe_remat(mbody, cfg.remat), (h, aux),
                                       params["moe_blocks"])
            return h, aux

        if cfg.family == "ssm":
            def body(hh, p):
                return self._ssm_block(p, hh), None
            (h), _ = jax.lax.scan(maybe_remat(body, cfg.remat), h,
                                  params["blocks"])
            return h, aux0

        if cfg.family == "hybrid":
            shared = params["shared_attn"]
            P = cfg.hybrid.shared_attn_period

            def period(hh, p):
                hh = self._shared_attn_block(shared, hh, positions)
                for i in range(P):
                    pi = jax.tree_util.tree_map(lambda x: x[i], p)
                    hh = self._ssm_block(pi, hh)
                return hh, None

            h, _ = jax.lax.scan(maybe_remat(period, cfg.remat), h,
                                params["mamba"])
            return h, aux0
        raise ValueError(cfg.family)

    # ------------------------------------------------------------------ train
    def loss_fn(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        S = tokens.shape[-1]
        B = tokens.shape[0]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = self._embed_tokens(params, batch)
        h, aux = self.backbone(params, h, positions)
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        logits = self.shard(logits, ("batch", None, "vocab") if logits.ndim == 3
                            else ("batch", None, None, "vocab"))
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if cfg.num_codebooks:       # (B,S,K,V) vs targets (B,K,S)
            t = jnp.moveaxis(targets, 1, 2)
            m = mask[..., None] if mask is not None else None
            ce = softmax_cross_entropy(logits, t, jnp.broadcast_to(
                m, t.shape) if m is not None else None)
        else:
            ce = softmax_cross_entropy(logits, targets, mask)
        loss = ce
        metrics = {"ce": ce}
        if cfg.moe is not None:
            loss = loss + 0.01 * aux
            metrics["aux"] = aux
        if cfg.mtp_depth and "mtp" in params:
            mtp_loss = self._mtp_loss(params, h, batch)
            loss = loss + 0.3 * mtp_loss
            metrics["mtp"] = mtp_loss
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, h, batch):
        """DeepSeek-V3 multi-token prediction (depth 1, simplified): at
        position i combine h_i with emb(t_{i+1}) to predict t_{i+2}."""
        cfg = self.cfg
        p = params["mtp"]
        tokens, targets = batch["tokens"], batch["targets"]
        e_next = embed(params["embed"], tokens[:, 1:])
        h_in = jnp.concatenate([
            rmsnorm(p["ln"], h[:, :-1], cfg.norm_eps), e_next], axis=-1)
        h_in = (h_in @ p["proj"]).astype(h.dtype)
        B, S1 = tokens.shape[0], tokens.shape[1] - 1
        positions = jnp.broadcast_to(jnp.arange(S1, dtype=jnp.int32), (B, S1))
        hm, _ = self._dense_block(p["block"], h_in, positions, "G",
                                  jnp.zeros((), jnp.float32))
        logits = self._logits(params, rmsnorm(params["final_ln"], hm,
                                              cfg.norm_eps))
        t = targets[:, 1:]
        mask = batch.get("loss_mask")
        m = mask[:, 1:] if mask is not None else None
        return softmax_cross_entropy(logits, t, m)

    # ---------------------------------------------------------------- caches
    def cache_specs(self, batch: int, max_len: int):
        """ParamSpec pytree describing the decode cache (dry-run friendly)."""
        cfg = self.cfg
        dt = cfg.dtype
        seq_ax = "seq" if cfg.seq_shard_attn else None
        bx = "batch"

        def kv(n_layers_stack, T):
            shape = tuple(n_layers_stack) + (batch, T, cfg.num_kv_heads,
                                             cfg.head_dim)
            axes = (None,) * len(n_layers_stack) + (bx, seq_ax, "heads", None)
            return {"k": ParamSpec(shape, axes, init="zeros", dtype=dt),
                    "v": ParamSpec(shape, axes, init="zeros", dtype=dt)}

        if cfg.family in ("dense", "audio", "vlm"):
            W = min(cfg.sliding_window or max_len, max_len)
            if cfg.local_global_pattern:
                pat = cfg.local_global_pattern
                Pn = len(pat)
                n_per, n_tail = divmod(cfg.num_layers, Pn)
                nL = sum(1 for k in pat if k == "L")
                nG = Pn - nL
                out = {"periods_local": kv((n_per, nL), W),
                       "periods_global": kv((n_per, nG), max_len)}
                if n_tail:
                    out["tail"] = kv((n_tail,), W if pat[0] == "L" else max_len)
                return out
            T = W if cfg.sliding_window else max_len
            return {"layers": kv((cfg.num_layers,), T)}
        if cfg.family == "moe":
            m = cfg.mla
            nd = cfg.moe.first_dense_layers
            L = cfg.num_layers
            if cfg.attention == "mla":
                def mla_cache(n):
                    return {
                        "ckv": ParamSpec((n, batch, max_len, m.kv_lora_rank),
                                         (None, bx, seq_ax, None), init="zeros",
                                         dtype=dt),
                        "kr": ParamSpec((n, batch, max_len, m.rope_head_dim),
                                        (None, bx, seq_ax, None), init="zeros",
                                        dtype=dt),
                    }
                out = {"moe_layers": mla_cache(L - nd)}
                if nd:
                    out["dense_layers"] = mla_cache(nd)
                return out
            out = {"moe_layers": kv((L - nd,), max_len)}
            if nd:
                out["dense_layers"] = kv((nd,), max_len)
            return out
        if cfg.family == "ssm":
            s = cfg.ssm
            conv_dim = cfg.expand_dim + 2 * s.n_groups * s.d_state
            return {
                "state": ParamSpec((cfg.num_layers, batch, cfg.ssm_heads,
                                    s.d_state, s.head_dim),
                                   (None, bx, "heads", None, None),
                                   init="zeros", dtype="float32"),
                "conv": ParamSpec((cfg.num_layers, batch, s.conv_kernel - 1,
                                   conv_dim),
                                  (None, bx, None, "model"), init="zeros",
                                  dtype=dt),
            }
        if cfg.family == "hybrid":
            s = cfg.ssm
            hb = cfg.hybrid
            P = hb.shared_attn_period
            n_per = cfg.num_layers // P
            conv_dim = cfg.expand_dim + 2 * s.n_groups * s.d_state
            hd = cfg.d_model // hb.shared_attn_heads
            return {
                "attn_k": ParamSpec((n_per, batch, max_len,
                                     hb.shared_attn_kv_heads, hd),
                                    (None, bx, seq_ax, "heads", None),
                                    init="zeros", dtype=dt),
                "attn_v": ParamSpec((n_per, batch, max_len,
                                     hb.shared_attn_kv_heads, hd),
                                    (None, bx, seq_ax, "heads", None),
                                    init="zeros", dtype=dt),
                "state": ParamSpec((n_per, P, batch, cfg.ssm_heads, s.d_state,
                                    s.head_dim),
                                   (None, None, bx, "heads", None, None),
                                   init="zeros", dtype="float32"),
                "conv": ParamSpec((n_per, P, batch, s.conv_kernel - 1, conv_dim),
                                  (None, None, bx, None, "model"),
                                  init="zeros", dtype=dt),
            }
        raise ValueError(cfg.family)

    def init_cache(self, batch: int, max_len: int):
        return materialize(self.cache_specs(batch, max_len),
                           jax.random.PRNGKey(0))

    # ------------------------------------------------------------------ decode
    def decode_step(self, params, cache, tokens, pos):
        """One token for the whole batch. tokens (B,) or (B,K); pos () int32.
        Returns (logits, new_cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        with jax.named_scope("embed"):
            if cfg.num_codebooks:
                h = None
                for k in range(cfg.num_codebooks):
                    e = embed(params["embed"][k], tokens[:, k][:, None])
                    h = e if h is None else h + e
            else:
                h = embed(params["embed"], tokens[:, None])     # (B,1,d)
            h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)

        def dense_step(p, hh, ck, cv, kind):
            window = cfg.sliding_window if kind == "L" else 0
            hn = rmsnorm(p["ln1"], hh, cfg.norm_eps)
            a, ck, cv = attn.gqa_decode(p["attn"], hn, ck, cv, pos, cfg,
                                        window=window, impl=self.attn_impl)
            hh = hh + a
            hn = rmsnorm(p["ln2"], hh, cfg.norm_eps)
            if "moe" in p:
                out, _ = moe_lib.moe_apply(p["moe"], hn, cfg, shard=self.shard)
            else:
                out = mlp(p["mlp"], hn, cfg.mlp)
            return hh + out, ck, cv

        def mla_step(p, hh, ckv, kr):
            hn = rmsnorm(p["ln1"], hh, cfg.norm_eps)
            a, ckv, kr = attn.mla_decode(p["attn"], hn, ckv, kr, pos, cfg)
            hh = hh + a
            hn = rmsnorm(p["ln2"], hh, cfg.norm_eps)
            if "moe" in p:
                out, _ = moe_lib.moe_apply(p["moe"], hn, cfg, shard=self.shard)
            else:
                out = mlp(p["mlp"], hn, cfg.mlp)
            return hh + out, ckv, kr

        if cfg.family in ("dense", "audio", "vlm"):
            if cfg.local_global_pattern:
                h, cache = self._decode_pattern(params, cache, h, pos, dense_step)
            else:
                kind = "L" if cfg.sliding_window else "G"

                def body(hh, xs):
                    p, ck, cv = xs
                    hh, ck, cv = dense_step(p, hh, ck, cv, kind)
                    return hh, (ck, cv)

                h, (ck, cv) = jax.lax.scan(
                    body, h, (params["blocks"], cache["layers"]["k"],
                              cache["layers"]["v"]))
                cache = {"layers": {"k": ck, "v": cv}}
        elif cfg.family == "moe":
            new_cache = {}
            if "dense_blocks" in params:
                if cfg.attention == "mla":
                    def dbody(hh, xs):
                        p, ckv, kr = xs
                        hh, ckv, kr = mla_step(p, hh, ckv, kr)
                        return hh, (ckv, kr)
                    h, (ckv, kr) = jax.lax.scan(
                        dbody, h, (params["dense_blocks"],
                                   cache["dense_layers"]["ckv"],
                                   cache["dense_layers"]["kr"]))
                    new_cache["dense_layers"] = {"ckv": ckv, "kr": kr}
                else:
                    def dbody(hh, xs):
                        p, ck, cv = xs
                        hh, ck, cv = dense_step(p, hh, ck, cv, "G")
                        return hh, (ck, cv)
                    h, (ck, cv) = jax.lax.scan(
                        dbody, h, (params["dense_blocks"],
                                   cache["dense_layers"]["k"],
                                   cache["dense_layers"]["v"]))
                    new_cache["dense_layers"] = {"k": ck, "v": cv}
            if cfg.attention == "mla":
                def mbody(hh, xs):
                    p, ckv, kr = xs
                    hh, ckv, kr = mla_step(p, hh, ckv, kr)
                    return hh, (ckv, kr)
                h, (ckv, kr) = jax.lax.scan(
                    mbody, h, (params["moe_blocks"],
                               cache["moe_layers"]["ckv"],
                               cache["moe_layers"]["kr"]))
                new_cache["moe_layers"] = {"ckv": ckv, "kr": kr}
            else:
                def mbody(hh, xs):
                    p, ck, cv = xs
                    hh, ck, cv = dense_step(p, hh, ck, cv, "G")
                    return hh, (ck, cv)
                h, (ck, cv) = jax.lax.scan(
                    mbody, h, (params["moe_blocks"], cache["moe_layers"]["k"],
                               cache["moe_layers"]["v"]))
                new_cache["moe_layers"] = {"k": ck, "v": cv}
            cache = new_cache
        elif cfg.family == "ssm":
            def body(hh, xs):
                p, st, cs = xs
                hn = rmsnorm(p["ln"], hh, cfg.norm_eps)
                out, st, cs = ssm_lib.mamba2_decode_step(p["ssm"], hn, st, cs,
                                                         cfg)
                return hh + out, (st, cs)

            h, (st, cs) = jax.lax.scan(body, h, (params["blocks"],
                                                 cache["state"], cache["conv"]))
            cache = {"state": st, "conv": cs}
        elif cfg.family == "hybrid":
            h, cache = self._decode_hybrid(params, cache, h, pos)
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)[:, 0]
        return logits, cache

    def _decode_pattern(self, params, cache, h, pos, dense_step):
        cfg = self.cfg
        pat = cfg.local_global_pattern

        def period_body(hh, xs):
            p, lk, lv, gk, gv = xs
            li = gi = 0
            lk_n, lv_n, gk_n, gv_n = lk, lv, gk, gv
            for i, kind in enumerate(pat):
                pi = jax.tree_util.tree_map(lambda x: x[i], p)
                if kind == "L":
                    hh, ck, cv = dense_step(pi, hh, lk_n[li], lv_n[li], "L")
                    lk_n = lk_n.at[li].set(ck)
                    lv_n = lv_n.at[li].set(cv)
                    li += 1
                else:
                    hh, ck, cv = dense_step(pi, hh, gk_n[gi], gv_n[gi], "G")
                    gk_n = gk_n.at[gi].set(ck)
                    gv_n = gv_n.at[gi].set(cv)
                    gi += 1
            return hh, (lk_n, lv_n, gk_n, gv_n)

        h, (lk, lv, gk, gv) = jax.lax.scan(
            period_body, h,
            (params["periods"], cache["periods_local"]["k"],
             cache["periods_local"]["v"], cache["periods_global"]["k"],
             cache["periods_global"]["v"]))
        new_cache = {"periods_local": {"k": lk, "v": lv},
                     "periods_global": {"k": gk, "v": gv}}
        if "tail" in params:
            def tail_body(hh, xs):
                p, ck, cv = xs
                hh, ck, cv = dense_step(p, hh, ck, cv, pat[0])
                return hh, (ck, cv)
            h, (tk, tv) = jax.lax.scan(
                tail_body, h, (params["tail"], cache["tail"]["k"],
                               cache["tail"]["v"]))
            new_cache["tail"] = {"k": tk, "v": tv}
        return h, new_cache

    def _decode_hybrid(self, params, cache, h, pos):
        cfg = self.cfg
        hb = cfg.hybrid
        P = hb.shared_attn_period
        shared = params["shared_attn"]
        sub = cfg.with_(num_heads=hb.shared_attn_heads,
                        num_kv_heads=hb.shared_attn_kv_heads,
                        head_dim=cfg.d_model // hb.shared_attn_heads)

        def period_body(hh, xs):
            p, ak, av, st, cs = xs
            hn = rmsnorm(shared["ln1"], hh, cfg.norm_eps)
            a, ak, av = attn.gqa_decode(shared["attn"], hn, ak, av, pos, sub,
                                        impl=self.attn_impl)
            hh = hh + a
            if "mlp" in shared:
                hn = rmsnorm(shared["ln2"], hh, cfg.norm_eps)
                hh = hh + mlp(shared["mlp"], hn, cfg.mlp)
            st_n, cs_n = st, cs
            for i in range(P):
                pi = jax.tree_util.tree_map(lambda x: x[i], p)
                hn = rmsnorm(pi["ln"], hh, cfg.norm_eps)
                out, sti, csi = ssm_lib.mamba2_decode_step(
                    pi["ssm"], hn, st_n[i], cs_n[i], cfg)
                st_n = st_n.at[i].set(sti)
                cs_n = cs_n.at[i].set(csi)
                hh = hh + out
            return hh, (ak, av, st_n, cs_n)

        h, (ak, av, st, cs) = jax.lax.scan(
            period_body, h, (params["mamba"], cache["attn_k"],
                             cache["attn_v"], cache["state"], cache["conv"]))
        return h, {"attn_k": ak, "attn_v": av, "state": st, "conv": cs}

    # ----------------------------------------------------------------- prefill
    def prefill(self, params, batch):
        """Forward over a prompt, returning (last-token logits, cache of len S).

        Uses the training backbone for hidden states (identical math) and a
        second pass of cheap projections for the cache; decode then continues
        from position S.  (Lowered for the prefill_* dry-run cells.)
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape[0], tokens.shape[-1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = self._embed_tokens(params, batch)
        h, caches = self._backbone_with_cache(params, h, positions)
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h[:, -1:])[:, 0]
        return logits, caches

    def _backbone_with_cache(self, params, h, positions):
        cfg = self.cfg

        def dense_prefill(p, hh, kind):
            window = cfg.sliding_window if kind == "L" else 0
            hn = rmsnorm(p["ln1"], hh, cfg.norm_eps)
            if cfg.attention == "mla":
                a, kvc = attn.mla_prefill(p["attn"], hn, positions, cfg,
                                          impl=self.attn_impl)
            else:
                a, kvc = attn.gqa_prefill(p["attn"], hn, positions, cfg,
                                          window=window, impl=self.attn_impl)
            hh = hh + a
            hn = rmsnorm(p["ln2"], hh, cfg.norm_eps)
            if "moe" in p:
                out, _ = moe_lib.moe_apply(p["moe"], hn, cfg, shard=self.shard)
            else:
                out = mlp(p["mlp"], hn, cfg.mlp)
            return hh + out, kvc

        if cfg.family in ("dense", "audio", "vlm") and not cfg.local_global_pattern:
            kind = "L" if cfg.sliding_window else "G"

            def body(hh, p):
                hh, (k, v) = dense_prefill(p, hh, kind)
                return hh, (k, v)

            h, (k, v) = jax.lax.scan(body, h, params["blocks"])
            return h, {"layers": {"k": k, "v": v}}
        if cfg.family in ("dense", "audio", "vlm"):
            pat = cfg.local_global_pattern

            def pbody(hh, p):
                lks, lvs, gks, gvs = [], [], [], []
                for i, kind in enumerate(pat):
                    pi = jax.tree_util.tree_map(lambda x: x[i], p)
                    hh, (k, v) = dense_prefill(pi, hh, kind)
                    (lks if kind == "L" else gks).append(k)
                    (lvs if kind == "L" else gvs).append(v)
                return hh, (jnp.stack(lks), jnp.stack(lvs),
                            jnp.stack(gks), jnp.stack(gvs))

            h, (lk, lv, gk, gv) = jax.lax.scan(pbody, h, params["periods"])
            out = {"periods_local": {"k": lk, "v": lv},
                   "periods_global": {"k": gk, "v": gv}}
            if "tail" in params:
                def tbody(hh, p):
                    hh, (k, v) = dense_prefill(p, hh, pat[0])
                    return hh, (k, v)
                h, (tk, tv) = jax.lax.scan(tbody, h, params["tail"])
                out["tail"] = {"k": tk, "v": tv}
            return h, out
        if cfg.family == "moe":
            out = {}
            if "dense_blocks" in params:
                def dbody(hh, p):
                    hh, kvc = dense_prefill(p, hh, "G")
                    return hh, kvc
                h, kvc = jax.lax.scan(dbody, h, params["dense_blocks"])
                out["dense_layers"] = ({"ckv": kvc[0], "kr": kvc[1]}
                                       if cfg.attention == "mla"
                                       else {"k": kvc[0], "v": kvc[1]})

            def mbody(hh, p):
                hh, kvc = dense_prefill(p, hh, "G")
                return hh, kvc

            h, kvc = jax.lax.scan(mbody, h, params["moe_blocks"])
            out["moe_layers"] = ({"ckv": kvc[0], "kr": kvc[1]}
                                 if cfg.attention == "mla"
                                 else {"k": kvc[0], "v": kvc[1]})
            return h, out
        if cfg.family == "ssm":
            K = cfg.ssm.conv_kernel

            def body(hh, p):
                hn = rmsnorm(p["ln"], hh, cfg.norm_eps)
                out, st, conv_tail = ssm_lib_prefill(p["ssm"], hn, cfg,
                                                     self.attn_impl)
                return hh + out, (st, conv_tail)

            h, (st, conv) = jax.lax.scan(body, h, params["blocks"])
            return h, {"state": st, "conv": conv}
        if cfg.family == "hybrid":
            hb = cfg.hybrid
            P = hb.shared_attn_period
            shared = params["shared_attn"]
            sub = cfg.with_(num_heads=hb.shared_attn_heads,
                            num_kv_heads=hb.shared_attn_kv_heads,
                            head_dim=cfg.d_model // hb.shared_attn_heads)

            def period(hh, p):
                hn = rmsnorm(shared["ln1"], hh, cfg.norm_eps)
                a, (ak, av) = attn.gqa_prefill(shared["attn"], hn, positions,
                                               sub, impl=self.attn_impl)
                hh = hh + a
                if "mlp" in shared:
                    hn = rmsnorm(shared["ln2"], hh, cfg.norm_eps)
                    hh = hh + mlp(shared["mlp"], hn, cfg.mlp)
                sts, convs = [], []
                for i in range(P):
                    pi = jax.tree_util.tree_map(lambda x: x[i], p)
                    hn = rmsnorm(pi["ln"], hh, cfg.norm_eps)
                    out, st, ct = ssm_lib_prefill(pi["ssm"], hn, cfg,
                                                  self.attn_impl)
                    hh = hh + out
                    sts.append(st)
                    convs.append(ct)
                return hh, (ak, av, jnp.stack(sts), jnp.stack(convs))

            h, (ak, av, st, conv) = jax.lax.scan(period, h, params["mamba"])
            return h, {"attn_k": ak, "attn_v": av, "state": st, "conv": conv}
        raise ValueError(cfg.family)


@jax.named_scope("ssm")
def ssm_lib_prefill(p, hn, cfg, attn_impl):
    """Mamba2 prefill: forward + (final ssm state, conv tail)."""
    s = cfg.ssm
    zxbcdt = hn @ p["in_proj"]
    z, x, Bm, Cm, dt = ssm_lib._split_proj(zxbcdt, cfg)
    xbc_raw = jnp.concatenate([x, Bm, Cm], axis=-1)
    K = s.conv_kernel
    conv_tail = jnp.pad(xbc_raw, ((0, 0), (max(0, K - 1 - xbc_raw.shape[1]), 0),
                                  (0, 0)))[:, -(K - 1):]
    xbc = ssm_lib._causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    d_in, G, N, nh = cfg.expand_dim, s.n_groups, s.d_state, cfg.ssm_heads
    xh = xbc[..., :d_in].reshape(*hn.shape[:2], nh, s.head_dim)
    Bh = xbc[..., d_in:d_in + G * N].reshape(*hn.shape[:2], G, N)
    Ch = xbc[..., d_in + G * N:].reshape(*hn.shape[:2], G, N)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_final = ssm_lib.ssd_chunked(xh, dtf, A, Bh, Ch, chunk=s.chunk_size)
    y = y + xh * p["D"][:, None].astype(xh.dtype)
    y = y.reshape(*hn.shape[:2], d_in)
    y = ssm_lib._gated_norm(p["norm"], y, z, cfg.norm_eps)
    return y @ p["out_proj"], h_final, conv_tail


def build_model(cfg: ModelConfig, shard_fn: Callable = Identity,
                attn_impl: str = "xla") -> Model:
    return Model(cfg, shard_fn=shard_fn, attn_impl=attn_impl)
