"""staging_pack — egress pack (+ optional int8 quantize) Pallas TPU kernel.

The paper's RDMA *block* becomes a VMEM-resident tile: the kernel re-tiles a
2D tensor into block-major layout so every transfer block is contiguous in
HBM (one DMA descriptor per block on egress), optionally fusing symmetric
int8 quantization (per-block scale) — the paper's §6 "data reduction at
staging", pushed all the way into the producing chip.

Tile shape obeys TPU packing: lanes = 128, sublanes a multiple of
32 bytes / itemsize. Grid = (rows/TR, cols/TC); out block n = i·ncols + j.

Mosaic wants the last two dims of every block (8k, 128)-aligned or whole:
block n is the whole (TR, TC) tile [n] of an (n_blocks, TR, TC) output,
which reshapes to (n_blocks, TR·TC) without an in-kernel relayout; scale n
is element n % 1024 of a lane-dense (8, 128) f32 tile that consecutive
grid steps revisit, so the scales cost 4 bytes per block in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SCALE_TILE = (8, 128)
_SCALES_PER_TILE = _SCALE_TILE[0] * _SCALE_TILE[1]


def _pack_kernel(x_ref, o_ref, s_ref, *, quantize: bool, nj: int):
    n = pl.program_id(0) * nj + pl.program_id(1)
    slot = n % _SCALES_PER_TILE

    @pl.when(slot == 0)
    def _():
        s_ref[...] = jnp.ones(_SCALE_TILE, jnp.float32)

    if quantize:
        x32 = x_ref[...].astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(x32 / scale), -127, 127)
        o_ref[0] = q.astype(o_ref.dtype)
        pos = (jax.lax.broadcasted_iota(jnp.int32, _SCALE_TILE, 0)
               * _SCALE_TILE[1]
               + jax.lax.broadcasted_iota(jnp.int32, _SCALE_TILE, 1))
        s_ref[...] = jnp.where(pos == slot, scale, s_ref[...])
    else:
        o_ref[0] = x_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "interpret"))
def pack_blocks(x: jax.Array, *, tile: tuple[int, int] = (256, 128),
                out_dtype=None, interpret: bool = False):
    """x: (R, C) with R % tile[0] == 0 == C % tile[1] (ops.py pads).

    Returns (blocks (n_blocks, TR*TC) out_dtype, scales (n_blocks,) f32).
    out_dtype int8 -> fused quantization.
    """
    R, C = x.shape
    TR, TC = tile
    assert R % TR == 0 and C % TC == 0, (x.shape, tile)
    ni, nj = R // TR, C // TC
    nb = ni * nj
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    quantize = out_dtype == jnp.int8
    n_scale_tiles = -(-nb // _SCALES_PER_TILE)
    # double-buffered in + out blocks, plus f32 temporaries when quantizing
    blk = TR * TC
    vmem = 2 * blk * (x.dtype.itemsize + out_dtype.itemsize)
    if quantize:
        vmem += 3 * blk * 4
    vmem += 4 * _SCALES_PER_TILE * 4 + (4 << 20)

    blocks, scales = pl.pallas_call(
        functools.partial(_pack_kernel, quantize=quantize, nj=nj),
        grid=(ni, nj),
        in_specs=[pl.BlockSpec((TR, TC), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((1, TR, TC), lambda i, j: (i * nj + j, 0, 0)),
            pl.BlockSpec(_SCALE_TILE,
                         lambda i, j: ((i * nj + j) // _SCALES_PER_TILE, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, TR, TC), out_dtype),
            jax.ShapeDtypeStruct((n_scale_tiles * _SCALE_TILE[0],
                                  _SCALE_TILE[1]), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(x)
    return blocks.reshape(nb, TR * TC), scales.reshape(-1)[:nb]
