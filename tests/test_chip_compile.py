"""Compile the serve path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler builds each program for a chip that is
described, not attached, and refuses what the chip would refuse (Mosaic
tiling, VMEM budget) — what interpret mode never checks.  The topology
is described inside a fixture, never at import: only one process at a
time may load the TPU library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.models import build_model
from repro.models.flags import Flags


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but not read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,H,KV,hd,P,T,MP", [
    (8, 12, 2, 128, 64, 32, 16),
    (4, 12, 2, 128, 256, 16, 32),
])
def test_paged_kernel_compiles_for_v5e(one_chip, B, H, KV, hd, P, T, MP):
    """qwen2-1.5b head geometry (12 heads, 2 KV heads, head_dim 128)."""
    pages = _sds((P, T, KV, hd), jnp.bfloat16, one_chip)
    compiled = paged_attention.lower(
        _sds((B, H, hd), jnp.bfloat16, one_chip), pages, pages,
        _sds((B, MP), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_paged_compiles_for_v5e(one_chip, monkeypatch):
    """A 2-layer qwen2-1.5b at full width decodes through the kernel:
    the dispatcher is steered off its CPU fallback, as on a TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), num_layers=2)
    model = build_model(cfg, Flags(remat=False))
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), model.abstract_params())
    B, P, T, MP = 8, 128, 32, 32
    pool = _sds((P, cfg.num_layers, 2, T, cfg.num_kv_heads, cfg.head_dim_),
                jnp.bfloat16, one_chip)
    # as the engine jits it: the pool donated, so the step's output pool
    # takes the donated buffer
    compiled = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
        params, pool, _sds((B, MP), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((B, 1), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "input_output_alias" in text
