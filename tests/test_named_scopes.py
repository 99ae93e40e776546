"""Every model block's ops carry a ``jax.named_scope`` in their name stack,
so the device trace's time can be read by block: the shared layer and
optimizer functions open the scopes, and no call site does."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import job_config
from repro.launch.steps import make_train_step, train_state_specs
from repro.models.model import build_model
from repro.models.param import abstract
from repro.optim import AdamW, cosine_schedule


def _op_names(lowered) -> set:
    """The name-stack locations of a lowered program's ops."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))


def _has_scope(names, scope: str) -> bool:
    pat = re.compile(rf"(^|/|\(){scope}(/|\))")
    return any(pat.search(n) for n in names)


@pytest.fixture(scope="module")
def model():
    return build_model(job_config("starcoder2-3b", smoke=True))


def test_train_step_ops_carry_block_scopes(model):
    opt = AdamW(cosine_schedule(1e-3, 1, 10))
    state = abstract(train_state_specs(model, opt))
    B, S = 2, 32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    names = _op_names(jax.jit(make_train_step(model, opt)).lower(state,
                                                                 batch))
    for scope in ("embed", "attention", "mlp", "norm", "head", "optimizer"):
        assert _has_scope(names, scope), scope
    # the backward pass keeps the forward's scopes under its transforms
    assert any("transpose(" in n or "jvp(" in n for n in names)
    assert not _has_scope(names, "kv_write")


def test_decode_step_ops_carry_block_scopes(model):
    params = abstract(model.param_specs())
    cache = abstract(model.cache_specs(2, 16))
    names = _op_names(jax.jit(model.decode_step).lower(
        params, cache, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
    for scope in ("embed", "attention", "kv_write", "mlp", "norm", "head"):
        assert _has_scope(names, scope), scope
    # the cache writes are the only ops of kv_write: nested in attention
    assert all(re.search(r"(^|/)attention/kv_write/", n)
               for n in names if _has_scope([n], "kv_write"))
    assert not _has_scope(names, "optimizer")
