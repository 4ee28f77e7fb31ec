"""The main-path Pallas kernels compile for a TPU v5e at Granite-3.0-1B-A400M
widths (d_model 1024, d_ff_expert 512, 32 experts, bf16).

Nothing runs: the TPU compiler, which ships with the installed libtpu,
compiles for a described ``v5e:2x2`` topology with no chip attached. It
refuses what interpret mode accepts (block shapes off the (8, 128) tile
grid, too much VMEM). The topology is described inside a fixture only,
never while a module is imported: only one process at a time may load
libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_ffn import grouped_ffn, moe_ffn

D, F, E = 1024, 512, 32
TILES = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weights(sharding):
    return (_sds((E, D, F), jnp.bfloat16, sharding),
            _sds((E, D, F), jnp.bfloat16, sharding),
            _sds((E, F, D), jnp.bfloat16, sharding))


@pytest.mark.parametrize("block_t", [8, 128])
def test_grouped_ffn_compiles_for_v5e(one_chip, block_t):
    """The sorted-dispatch grouped GEMM (served prefill path) compiles
    to a Mosaic kernel, not an XLA fallback."""
    fn = jax.jit(lambda xs, w1, w3, w2, eid, valid: grouped_ffn(
        xs, w1, w3, w2, eid, valid, block_t=block_t, interpret=False))
    compiled = fn.lower(
        _sds((TILES * block_t, D), jnp.bfloat16, one_chip),
        *_weights(one_chip),
        _sds((TILES,), jnp.int32, one_chip),
        _sds((TILES,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_ffn_compiles_for_v5e(one_chip):
    """The masked decode kernel: its per-expert (T, 1) gate column is a
    full-extent block of an (E, T, 1) array, which the lowering accepts."""
    T = 16
    fn = jax.jit(lambda x, w1, w3, w2, comb, act: moe_ffn(
        x, w1, w3, w2, comb, act, max_active=16, interpret=False))
    compiled = fn.lower(
        _sds((T, D), jnp.bfloat16, one_chip), *_weights(one_chip),
        _sds((T, E), jnp.float32, one_chip),
        _sds((E,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
