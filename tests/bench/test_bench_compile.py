"""Compile, for a described TPU v5e, the paged kernel and a two-layer
``decode_step_paged`` at qwen2-7b widths (28 q heads over 4 KV heads,
groups of 7: a head geometry the cells' qwen2-1.5b does not have).
Nothing runs; the topology is described inside a fixture, never at
import."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(t.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def qwen2_7b(layers):
    """The Qwen2 registration at Qwen2-7B's published widths (hf
    Qwen/Qwen2-7B)."""
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config("qwen2-1.5b"), name="qwen2-7b", num_layers=layers,
        d_model=3584, num_heads=28, num_kv_heads=4, d_ff=18944,
        vocab_size=152064, head_dim=128, rope_theta=1e6, norm_eps=1e-6)


def test_paged_kernel_compiles_at_qwen2_7b_heads(one_chip):
    from repro.kernels.paged_attention import paged_attention
    B, H, KV, hd, P, T, MP = 16, 28, 4, 128, 512, 32, 80
    pages = _sds((P, T, KV, hd), jnp.bfloat16, one_chip)
    compiled = paged_attention.lower(
        _sds((B, H, hd), jnp.bfloat16, one_chip), pages, pages,
        _sds((B, MP), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_two_layer_decode_step_compiles_at_qwen2_7b_widths(one_chip,
                                                           monkeypatch):
    from repro.kernels import ops
    from repro.models import build_model
    from repro.models.flags import Flags
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = qwen2_7b(layers=2)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff) == (
        3584, 28, 4, 18944)
    model = build_model(cfg, Flags(remat=False))
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), model.abstract_params())
    B, P, T, MP = 16, 256, 32, 80
    pool = _sds((P, cfg.num_layers, 2, T, cfg.num_kv_heads, cfg.head_dim_),
                jnp.bfloat16, one_chip)
    compiled = jax.jit(model.decode_step_paged).lower(
        params, pool, _sds((B, MP), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((B, 1), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
