"""Operations and bytes of a dense decoder (GQA attention, a two-matrix
MLP), computed from a configuration file's sizes.

Model FLOPs count what the mathematics needs: two per multiply-add of every
matmul parameter of the layers and the output head (not the input
embedding, which is a gather), plus the attention products over the keys
each query may see under the causal and window mask.  Recomputation under
remat is not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def _sizes(cfg: Dict[str, Any]):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg.get("sliding_window") or 0)


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d, H, Hkv, hd, ff, _, _, _ = _sizes(cfg)
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 2 * d * ff


def matmul_params(cfg: Dict[str, Any]) -> int:
    """N: the matmul parameters of the layers and the output head."""
    d, *_, V, L, _ = _sizes(cfg)
    return L * layer_matmul_params(cfg) + d * V


def mean_keys(seq_len: int, window: int) -> float:
    """Mean number of keys a query sees, causal, within ``window``."""
    w = window or seq_len
    n = min(seq_len, w)
    # positions i < w see i + 1 keys, the rest see w
    return (n * (n + 1) / 2 + (seq_len - n) * w) / seq_len


def attention_flops_per_token(cfg: Dict[str, Any], keys: float) -> float:
    """Forward QK^T and PV of one token over ``keys`` keys, all layers."""
    d, H, Hkv, hd, ff, V, L, _ = _sizes(cfg)
    return 4.0 * H * hd * keys * L


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 N plus three times the forward attention."""
    keys = mean_keys(seq_len, cfg.get("sliding_window") or 0)
    return 6.0 * matmul_params(cfg) + 3.0 * attention_flops_per_token(cfg, keys)


def forward_flops_per_token(cfg: Dict[str, Any], keys: float) -> float:
    return 2.0 * matmul_params(cfg) + attention_flops_per_token(cfg, keys)


def decode_bytes(cfg: Dict[str, Any], batch: int, keys: float,
                 weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Least bytes one decode step of ``batch`` sequences must move: every
    layer weight and the head once, the embedding rows of the batch, the
    ``keys`` valid cached keys and values of every sequence and layer, the
    new key and value written, and the f32 logits out."""
    d, H, Hkv, hd, ff, V, L, _ = _sizes(cfg)
    weights = matmul_params(cfg) * weight_bytes
    biases = L * (H + 2 * Hkv) * hd * weight_bytes
    norms = (2 * L + 1) * d * 4
    embed_rows = batch * d * weight_bytes
    kv = 2 * L * batch * Hkv * hd * cache_bytes * (keys + 1)
    logits = batch * V * 4
    return float(weights + biases + norms + embed_rows + kv + logits)
