"""Public wrapper: tile choice and platform dispatch for the decode matmul.

On a TPU the Pallas kernel runs compiled (Mosaic); on any other platform
the same kernel runs in interpret mode, so CPU tests run its tiling and
masking. The choice is made when the program is lowered, not when it is
traced, so a compile for a described TPU gets the compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_matmul import kernel

#: bytes of one weight tile (double-buffered in VMEM)
TILE_BYTES = 4 << 20
#: widest N tile, in columns
MAX_BLOCK_N = 2048


def _fit(dim: int, cap: int) -> int:
    """The whole `dim` if it fits `cap`, else the widest multiple of 128
    lanes up to `cap` that divides `dim` (or, if none does, that fits)."""
    if dim <= cap:
        return dim
    cap = max(128, cap - cap % 128)
    for b in range(cap, 127, -128):
        if dim % b == 0:
            return b
    return cap


def tiles(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(block_k, block_n) for a (k, n) weight of `itemsize`-byte elements."""
    block_n = _fit(n, MAX_BLOCK_N)
    return _fit(k, TILE_BYTES // (block_n * itemsize)), block_n


def decode_matmul(x: jax.Array, w: jax.Array, layer=None, *,
                  name: str = "decode_matmul") -> jax.Array:
    """x: (M, K); w: (K, N), or a stack (L, K, N) of which `layer` picks
    one. Returns x @ w.astype(x.dtype), float32-accumulated, in x's dtype."""
    if w.ndim == 2:
        w, layer = w[None], 0
    block_k, block_n = tiles(w.shape[1], w.shape[2], w.dtype.itemsize)
    call = functools.partial(kernel.matmul_cast, block_k=block_k,
                             block_n=block_n, name=name)
    return jax.lax.platform_dependent(
        x, w, jnp.asarray(layer, jnp.int32), tpu=call,
        default=functools.partial(call, interpret=True))
