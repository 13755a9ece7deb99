"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e chip, at real sizes.

Nothing runs: the TPU compiler (Mosaic) only has to accept each kernel,
which interpret-mode tests cannot show (block alignment, VMEM budget).
The topology is described inside a fixture, so every test worker collects
the same tests and only the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

FIELD = (201, 501, 501)  # one step of the paper's velocity mesh, float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_quantize_blocks_pallas_compiles_at_paper_field(one_chip):
    from repro.kernels.staging_pack import ops
    x = jax.ShapeDtypeStruct(FIELD, jnp.float32, sharding=one_chip)
    txt = _compiled_text(
        lambda a: ops.quantize_blocks(a, block_elems=4096, impl="pallas"), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("out_dtype", [None, jnp.int8])
def test_pack_pallas_compiles_at_4mb_blocks(one_chip, out_dtype):
    from repro.kernels.staging_pack import ops
    x = jax.ShapeDtypeStruct(FIELD, jnp.float32, sharding=one_chip)
    txt = _compiled_text(
        lambda a: ops.pack(a, block_bytes=4 << 20, out_dtype=out_dtype,
                           impl="pallas"), x)
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_at_musicgen_width(one_chip):
    from repro.configs import get_config
    from repro.kernels.flash_attention import ops
    cfg = get_config("musicgen-medium")
    shape = (4, 1024, cfg.n_heads, cfg.head_dim)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    txt = _compiled_text(
        lambda a, b, c: ops.gqa_attention(a, b, c, impl="pallas",
                                          block_q=128, block_k=128,
                                          causal=True),
        q, q, q)
    assert "tpu_custom_call" in txt
