"""Decode matmul — Pallas TPU kernel: ``y = x @ w.astype(x.dtype)`` that
reads a wide stored weight once, as stored.

Decode multiplies a few rows of activations by each weight matrix, so it is
bound by the weight's bytes. The weight's tiles are DMA'd from HBM in their
stored dtype (float32) and cast to the compute dtype in VMEM, rounding as
``astype`` does; the MXU accumulates in float32 and the result is written in
the compute dtype once the last K tile is in. No cast copy of the weight is
ever written to HBM.

``w`` is a stack of matrices ``(L, K, N)`` and ``layer`` picks one: the
index is prefetched into SMEM and the weight's BlockSpec reads only that
layer's tiles, so a layer scan passes the whole stacked parameter and no
per-layer slice is materialised outside the kernel.

Grid = (N tiles, K tiles); K is innermost and sequential, with the float32
accumulator in VMEM scratch across K steps. An N tile past the end is
written only where it lies inside ``N``; a K tile past the end is masked to
zero in both operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, k_dim: int,
                   block_k: int):
    del layer_ref  # read by the index maps
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    if k_dim % block_k:  # the last K tile runs past the end: zero its tail
        left = k_dim - k * block_k
        x = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < left,
                      x, jnp.zeros_like(x))
        w = jnp.where(jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) < left,
                      w, jnp.zeros_like(w))
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n", "name",
                                             "interpret"))
def matmul_cast(x: jax.Array, w: jax.Array, layer: jax.Array, *,
                block_k: int, block_n: int, name: str = "decode_matmul",
                interpret: bool = False) -> jax.Array:
    """x: (M, K) in the compute dtype; w: (L, K, N) in any float dtype;
    layer: int32 scalar. Returns (M, N) in x's dtype."""
    M, K = x.shape
    _, Kw, N = w.shape
    assert K == Kw, (x.shape, w.shape)
    # double-buffered x, w and output tiles, the cast w tile, the accumulator
    wt = block_k * block_n
    vmem = (2 * (M * block_k * x.dtype.itemsize + wt * w.dtype.itemsize
                 + M * block_n * x.dtype.itemsize)
            + wt * (x.dtype.itemsize + 4) + M * block_n * 4 + (4 << 20))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_dim=K, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(N, block_n), pl.cdiv(K, block_k)),
            in_specs=[
                pl.BlockSpec((M, block_k), lambda n, k, layer: (0, k)),
                pl.BlockSpec((None, block_k, block_n),
                             lambda n, k, layer: (layer[0], k, n)),
            ],
            out_specs=pl.BlockSpec((M, block_n), lambda n, k, layer: (0, n)),
            scratch_shapes=[pltpu.VMEM((M, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
