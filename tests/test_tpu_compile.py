"""Compile every Pallas kernel for a described (not attached) TPU v5e chip at
real widths.  Interpret-mode parity (test_kernels.py) cannot see the chip's
block-alignment and lowering rules; the TPU compiler can, without a chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test collection must not depend on it.
The module's tests share one xdist worker (``--dist loadfile`` keeps a file
on one worker; the group keeps it so under ``--dist loadgroup``).  The
tests skip only where the TPU library is not installed; any other failure to
describe the chip fails them.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import job_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_preprocess.fused_preprocess import fused_preprocess_fwd
from repro.kernels.ssd_scan import ssd_fwd
from repro.models.model import build_model
from repro.models.param import abstract

pytestmark = pytest.mark.xdist_group("tpu_compile")


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU library (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_starcoder2_widths(one_chip):
    B, S, H, Hkv, D = 2, 2048, 24, 2, 128
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 window=4096),
             one_chip, ((B, S, H, D), jnp.bfloat16),
             ((B, S, Hkv, D), jnp.bfloat16), ((B, S, Hkv, D), jnp.bfloat16))


def test_decode_attention_compiles_at_starcoder2_widths(one_chip):
    B, T, H, Hkv, D = 8, 4096, 24, 2, 128
    _compile(lambda q, k, v, pos: decode_attention(q, k, v, pos=pos,
                                                   window=4096),
             one_chip, ((B, H, D), jnp.bfloat16),
             ((B, T, Hkv, D), jnp.bfloat16), ((B, T, Hkv, D), jnp.bfloat16),
             ((), jnp.int32))


def test_ssd_compiles_at_mamba2_widths(one_chip):
    B, S, nh, P, G, N = 2, 2048, 64, 64, 1, 128
    _compile(lambda x, dt, A, b, c: ssd_fwd(x, dt, A, b, c, chunk=256),
             one_chip, ((B, S, nh, P), jnp.bfloat16), ((B, S, nh), jnp.float32),
             ((nh,), jnp.float32), ((B, S, G, N), jnp.bfloat16),
             ((B, S, G, N), jnp.bfloat16))


def test_fused_preprocess_compiles_at_224_crop(one_chip):
    mean, std = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)
    _compile(lambda im: fused_preprocess_fwd(im, (16, 16, 224, 224), mean,
                                             std),
             one_chip, ((8, 256, 256, 3), jnp.uint8))


def test_decode_step_holds_no_copy_of_the_stacked_cache(one_chip):
    """Left free, the chip's compiler lays the layer scan's carried KV cache
    out for the attention products at starcoder2-3b widths, and copies the
    whole cache into and out of the scan; the donated step must update the
    stacked cache in place, with no such copy."""
    model = build_model(job_config("starcoder2-3b", smoke=False,
                                   num_layers=2))
    B, T = 8, 64

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(model.abstract_params())
    cache = on_chip(abstract(model.cache_specs(B, T)))
    hlo = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((B,), jnp.int32,
                                            sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    dims = ",".join(map(str, cache["layers"]["k"].shape))
    assert re.search(rf"= bf16\[{dims}\]\S* dynamic-update-slice\(", hlo)
    copies = re.findall(rf"%\S+ = bf16\[{dims}\]\S* copy\(", hlo)
    assert not copies, copies
