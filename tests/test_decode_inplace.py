"""``Model.decode_step`` writes each layer's new KV entry into the stacked
cache in place: the layer scan carries the stacked (L,B,T,...) caches, so
the compiled step neither copies the whole cache nor slices a layer out and
stacks it back; and the result is bit for bit what a plain loop over the
layers, each on a cache of its own, computes."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, job_config, reduce_for_smoke
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.layers import mlp, rmsnorm
from repro.models.model import build_model
from repro.models.param import abstract

_INSTR = re.compile(r"^\s*(?:ROOT )?(%\S+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(([^)]*)\)")


def _instructions(hlo: str):
    """(name, dtype, dims, opcode, operand names) of every array-valued
    instruction of an optimized HLO module's text."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, dtype, dims, op, args = m.groups()
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append((name, dtype, shape, op,
                        re.findall(r"%[\w.\-]+", args)))
    return out


def test_donated_decode_step_has_no_whole_cache_or_layer_copies():
    model = build_model(job_config("starcoder2-3b", smoke=True))
    B, T = 8, 64
    cache = abstract(model.cache_specs(B, T))
    hlo = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        abstract(model.param_specs()), cache,
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    stacked = cache["layers"]["k"].shape                    # (L,B,T,Hkv,D)
    layer = stacked[1:]
    instrs = _instructions(hlo)
    shapes = {name: shape for name, _, shape, _, _ in instrs}
    assert any(op == "dynamic-update-slice" and shape == stacked
               for _, _, shape, op, _ in instrs), "no in-place cache write"
    for name, _, shape, op, args in instrs:
        if op in ("copy", "broadcast"):
            assert shape != stacked, f"{op} of the whole cache: {name}"
        if op == "dynamic-update-slice":
            update = tuple(d for d in shapes[args[1]] if d != 1)
            assert update != tuple(d for d in layer if d != 1), \
                f"a whole layer's cache stacked back: {name}"


def _layer_step(model, blocks, i, h, c0, c1, pos):
    """Block ``i`` of the decode step on the layer's own two caches."""
    cfg = model.cfg
    p = jax.tree_util.tree_map(lambda x: x[i], blocks)
    hn = rmsnorm(p["ln1"], h, cfg.norm_eps)
    if cfg.attention == "mla":
        a, c0, c1 = attn.mla_decode(p["attn"], hn, c0, c1, pos, cfg)
    else:
        window = cfg.sliding_window if cfg.family == "dense" else 0
        a, c0, c1 = attn.gqa_decode(p["attn"], hn, c0, c1, pos, cfg,
                                    window=window)
    h = h + a
    hn = rmsnorm(p["ln2"], h, cfg.norm_eps)
    if "moe" in p:
        return h + moe_lib.moe_apply(p["moe"], hn, cfg)[0], c0, c1
    return h + mlp(p["mlp"], hn, cfg.mlp), c0, c1


def _per_layer_decode(model, params, caches, tokens, pos):
    """The decode step as a plain loop over the layers, each attending to
    and writing a cache of its own (``caches``: group -> per-layer list of
    the attention function's two caches).  Each block is one jitted call,
    compiled alone as the layer scan's body is."""
    cfg = model.cfg
    step = jax.jit(functools.partial(_layer_step, model))
    h = jax.jit(model._embed_tokens)(params, {"tokens": tokens[:, None]})
    out = {}
    for group, blocks in (("layers", "blocks"),
                          ("dense_layers", "dense_blocks"),
                          ("moe_layers", "moe_blocks")):
        if blocks not in params:
            continue
        out[group] = []
        for i, (c0, c1) in enumerate(caches[group]):
            h, c0, c1 = step(params[blocks], jnp.int32(i), h, c0, c1, pos)
            out[group].append((c0, c1))
    head = jax.jit(lambda h: model._logits(
        params, rmsnorm(params["final_ln"], h, cfg.norm_eps))[:, 0])
    return head(h), out


def _config(arch):
    cfg = reduce_for_smoke(get_arch(arch))
    if arch == "starcoder2-3b":          # a ring of 5 slots, wrapped twice
        return cfg.with_(sliding_window=5)
    # two dense blocks before the experts, as a stack of one is unrolled
    return cfg.with_(num_layers=5, moe=dataclasses.replace(
        cfg.moe, first_dense_layers=2))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "granite-moe-1b-a400m",
                                  "deepseek-v3-671b"])
def test_decode_step_equals_per_layer_loop_bitwise(arch):
    cfg = _config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    B, S = 2, 12
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (B, S)).astype(np.int32)
    cache = model.init_cache(B, S)
    names = ("ckv", "kr") if cfg.attention == "mla" else ("k", "v")
    caches = {g: [(c[names[0]][i], c[names[1]][i])
                  for i in range(c[names[0]].shape[0])]
              for g, c in cache.items()}
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    for t in range(S):
        tok = jnp.asarray(tokens[:, t])
        logits, cache = step(params, cache, tok, jnp.int32(t))
        want, caches = _per_layer_decode(model, params, caches, tok,
                                         jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    for g, layers in caches.items():
        for n, name in enumerate(names):
            np.testing.assert_array_equal(
                np.asarray(cache[g][name]),
                np.stack([np.asarray(c[n]) for c in layers]))
