"""Ahead-of-time compiles of the main path's Pallas kernels, and of the
served decode step, for a described TPU v5e chip, at real sizes.

Nothing runs: the TPU compiler (Mosaic) only has to accept each kernel,
which interpret-mode tests cannot show (block alignment, VMEM budget); the
decode step's compiled program is read for what it allocates and copies.
The topology is described inside a fixture, so every test worker collects
the same tests and only the worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

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


@pytest.mark.parametrize("rows,k,n", [(16, 1536, 6144), (16, 6144, 1536),
                                        (16, 1536, 1536)])
def test_decode_matmul_compiles_at_musicgen_width(one_chip, rows, k, n):
    """The decode matmul at MusicGen's projection widths, reading one layer
    of a 48-layer float32 stack."""
    from repro.kernels.decode_matmul import ops
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((48, k, n), jnp.float32, sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    txt = _compiled_text(ops.decode_matmul, x, w, layer)
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def served_decode(topo):
    """The served decode step compiled at the musicgen cells' shapes:
    float32 parameters, bf16 compute, 16 requests, 750-token cache,
    donated. Returns (cfg, abstract params, B, T, compiled)."""
    from repro.configs import get_config
    from repro.models import Model
    from repro.train import ServeSetup
    B, T = 16, 750
    cfg = get_config("musicgen-medium")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    model = Model(cfg)
    setup = ServeSetup(model, mesh, global_batch=B)

    def described(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    params = described(model.abstract_params(), setup.param_shardings())
    cache = described(model.abstract_cache(B, T), setup.cache_shardings(B, T))
    rep = NamedSharding(mesh, PartitionSpec())
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rep),
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)}
    with jax.set_mesh(mesh):
        compiled = jax.jit(setup.decode_fn(), donate_argnums=(1,)).lower(
            params, cache, batch).compile()
    return cfg, params, B, T, compiled


def test_decode_updates_stacked_kv_cache_in_place(served_decode):
    """The served decode step writes its new tokens into the stacked caches
    in place: no copy and no whole-slice write of a stacked cache, and
    under 0.1 GB of temporaries (with the caches as the layer scan's
    outputs it takes 6.41 GB; with the weights cast outside the kernel,
    2.76 GB)."""
    cfg, _, B, T, compiled = served_decode
    stacked = f"bf16[{cfg.n_layers},{B},{T},{cfg.n_kv_heads * cfg.head_dim}]"
    op = re.compile(r"^\s*(?:ROOT )?%(\S+) = " + re.escape(stacked)
                    + r"\S* ([a-z-]+)\(")
    inserts, offenders = [], []
    for line in compiled.as_text().splitlines():
        m = op.match(line)
        if m is None or m.group(2) not in ("copy", "dynamic-update-slice",
                                           "fusion"):
            continue
        if m.group(2) == "fusion" and "/attn/kv_update/scatter" in line:
            inserts.append(m.group(1))
        else:
            offenders.append(m.group(1))
    assert not offenders, offenders
    assert len(inserts) == 2, inserts  # one scatter each into K and V
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.0e8, temp


def test_decode_reads_float32_weights_in_place(served_decode):
    """Each of the layer's six weight matmuls reads its float32 matrix
    straight out of the stacked parameter in the decode_matmul kernel: no
    value of a weight matrix's shape (a whole-stack cast, a layer's cast
    or a layer's slice) is written anywhere in the program. (XLA hoisted
    the casts out of the layer loop as whole-stack converts: 2.72 GB
    written and read again on every step.)"""
    cfg, params, _, _, compiled = served_decode
    L = cfg.n_layers
    mats = {a.shape[1:] for a in jax.tree.leaves(params["scan"])
            if a.ndim == 3}
    assert {(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)} <= mats
    shapes = "|".join(
        re.escape(f"[{d}{k},{n}]") for k, n in mats for d in ("", f"{L},"))
    weight_value = re.compile(
        r"^\s*(?:ROOT )?%(\S+) = (?:bf16|f32)(?:" + shapes + r")\S* "
        r"(?!parameter\(|get-tuple-element\(|bitcast\()")
    txt = compiled.as_text()
    written = [m.group(1) for m in map(weight_value.match, txt.splitlines())
               if m is not None]
    assert not written, written
    kernels = re.findall(r"%(decode_matmul_\w+?)\.\d+ = .*"
                         r'custom_call_target="tpu_custom_call"', txt)
    assert sorted(kernels) == sorted([
        "decode_matmul_attn_wq", "decode_matmul_attn_wk",
        "decode_matmul_attn_wv", "decode_matmul_attn_wo",
        "decode_matmul_mlp_wi", "decode_matmul_mlp_wo"]), kernels
