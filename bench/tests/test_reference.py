"""The plain reference against the program's model at a small size on the
CPU: the same weights from the same seed, and the same loss, gradient and
logits to float32 rounding."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import starcoder2 as ref
from bench.tests import tiny


def _program(dtype):
    from repro.configs import job_config
    from repro.models.model import build_model
    cfg = job_config("starcoder2-3b", smoke=True).with_(dtype=dtype)
    return build_model(cfg)


def _flat(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p, simple=True, separator="/"): x
            for p, x in flat}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_reference_builds_the_programs_weights_from_the_seed(dtype):
    model = _program(dtype)
    got = ref.init_params(ref.Sizes.of(dict(tiny.CONFIG, dtype=dtype)), 11)
    # both jitted, as Trainer and Server build them
    want = _flat(jax.jit(model.init)(jax.random.PRNGKey(11)))
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(want[k], np.float32), k)


def _batch(rng, B=2, S=64, V=512):
    block = rng.integers(0, V, (B, S + 1)).astype(np.int32)
    return {"tokens": block[:, :-1], "targets": block[:, 1:],
            "loss_mask": np.ones((B, S), np.float32)}


def test_loss_and_gradient_match_model_loss_fn():
    model = _program("float32")
    s = ref.Sizes.of(tiny.CONFIG)
    params = model.init(jax.random.PRNGKey(3))
    batch = {k: jnp.asarray(v) for k, v in
             _batch(np.random.default_rng(0)).items()}
    (want, _), gw = jax.value_and_grad(model.loss_fn, has_aux=True)(
        params, batch)
    rp = ref.init_params(s, 3)
    got, gg = jax.value_and_grad(lambda p: ref.loss(p, batch, s))(rp)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    gw = _flat(gw)
    for k in gw:
        np.testing.assert_allclose(np.asarray(gg[k]), np.asarray(gw[k]),
                                   rtol=2e-4, atol=1e-6, err_msg=k)


def test_logits_match_model_prefill():
    model = _program("float32")
    s = ref.Sizes.of(tiny.CONFIG)
    params = model.init(jax.random.PRNGKey(5))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (3, 64)),
                         jnp.int32)
    want, _ = model.prefill(params, {"tokens": tokens})
    rp = ref.init_params(s, 5)
    got = ref.logits_of(rp, ref.hidden(rp, tokens, s, ref.dot_f32)[:, -1], s,
                        ref.dot_f32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_a_window_shorter_than_the_sequence_masks_old_keys():
    s = ref.Sizes.of(dict(tiny.CONFIG, sliding_window=8))
    rp = ref.init_params(s, 2)
    tok = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 32)),
                      jnp.int32)
    far = tok.at[0, 0].set((tok[0, 0] + 7) % 512)
    # the last position sees keys 24..31 in every layer: a change at
    # position 0 reaches it through at most 4 layers of 7 positions each
    a = ref.hidden(rp, tok, s, ref.dot_f32)[0, -1]
    b = ref.hidden(rp, far, s, ref.dot_f32)[0, -1]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    near = tok.at[0, 30].set((tok[0, 30] + 7) % 512)
    c = ref.hidden(rp, near, s, ref.dot_f32)[0, -1]
    assert not np.array_equal(np.asarray(a), np.asarray(c))
