"""Lossy int8 block quantization: per-4096-element amax scales.

The same scheme as ``optim/grad_compress`` but applied to egress datasets:
each block of 4096 consecutive elements is scaled by ``amax/127`` and
rounded to int8, shrinking float64 payloads 8x (float32 4x) minus a 4-byte
scale per block.  The reconstruction error is bounded per block by
``|x - dq| <= scale/2 + spacing(amax)``: rint is within 1/2 of ``x/scale``
and ``|x/scale| <= 127`` by construction, so the clip never bites; the
float rounding of ``x/scale`` and ``q*scale`` adds the ``spacing`` term.

For jax device arrays the quantize+pack step lowers through the
``kernels/staging_pack`` quantizing variant (``ops.quantize_blocks``) so
bytes shrink *on device* before the host copy; numpy inputs take an
equivalent host path.  Non-float dtypes pass through unchanged
(``meta["passthrough"]``) — lossy quantization of index data would be
silent corruption.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from .base import Codec, as_bytes_array, np_dtype, register_codec

BLOCK = 4096  # elements per scale block; matches grad_compress.QBLOCK


def quantize(x: np.ndarray, block: int) -> Tuple[np.ndarray, np.ndarray]:
    """Quantize a flat float array in blocks of ``block`` elements.

    Returns ``(scales, q)``: one float32 ``amax/127`` scale per block (1.0
    for an all-zero block) and ``x.size`` int8 values. Float16 input is
    computed in float32, which keeps the bound of the module docstring.
    """
    n = x.size
    nb = -(-n // block)
    scales = np.ones(nb, np.float32)
    q = np.empty(nb * block, np.int8)
    if n:
        # float16 math would wreck the scale/2 bound; compute in >=f32.
        cdt = x.dtype if x.dtype.itemsize >= 4 else np.dtype(np.float32)
        xb = np.zeros(nb * block, cdt)
        xb[:n] = x
        xb = xb.reshape(nb, block)
        amax = np.max(np.abs(xb), axis=1)
        scales = (amax / np.float32(127.0)).astype(np.float32)
        scales[scales == 0] = 1.0
        q = np.clip(np.rint(xb / scales[:, None].astype(cdt)),
                    -127, 127).astype(np.int8).reshape(-1)
    return scales, q[:n]


def dequantize(scales: np.ndarray, q: np.ndarray, dtype,
               block: int) -> np.ndarray:
    """Invert :func:`quantize`: ``q.size`` values of ``dtype``, flat."""
    n = q.size
    nb = -(-n // block)
    dt = np.dtype(dtype)
    qb = np.zeros(nb * block, np.int8)
    qb[:n] = q
    cdt = dt if dt.itemsize >= 4 else np.dtype(np.float32)
    dq = (qb.reshape(nb, block).astype(cdt) *
          scales[:, None].astype(cdt)).reshape(-1)[:n]
    return dq.astype(dt)


def _device_array(data):
    """Return data if it is a jax device array, else None (no jax import
    unless the input plausibly needs it)."""
    if isinstance(data, (np.ndarray, bytes, bytearray, memoryview)):
        return None
    try:
        import jax
    except Exception:  # pragma: no cover - jax is a hard dep in this repo
        return None
    return data if isinstance(data, jax.Array) else None


@register_codec("int8-block")
class Int8BlockCodec(Codec):
    """Per-block int8 quantization; payload = f32 scales || int8 values.

    ``impl`` selects the device lowering for jax-array inputs: ``"xla"``
    (default, runs everywhere and keeps CPU CI honest) or ``"pallas"``
    (the fused staging_pack kernel, TPU).  Host numpy inputs always take
    the vectorized numpy path.
    """

    lossless = False
    chained = False

    def __init__(self, impl: str = "xla"):
        self.impl = impl

    def encode(self, data, *, dtype: str = "uint8",
               key: str = "") -> Tuple[Any, Dict[str, Any]]:
        dev = _device_array(data)
        if dev is not None:
            return self._encode_device(dev)
        if isinstance(data, np.ndarray) and data.dtype != np.uint8:
            arr = np.ascontiguousarray(data)
        else:
            # bytes-like input (or a flat uint8 view, which is how the
            # Communicator ships every dataset): reinterpret through the
            # declared dataset dtype
            dt = np_dtype(dtype)
            raw = as_bytes_array(data)
            if dt is None or dt.itemsize == 0 or raw.size % dt.itemsize:
                return self._passthrough(raw)
            arr = raw.view(dt)
        if arr.dtype.kind != "f" or arr.dtype.itemsize < 2:
            return self._passthrough(as_bytes_array(arr))
        n = arr.size
        scales, q = quantize(arr.reshape(-1), BLOCK)
        payload = scales.tobytes() + q.tobytes()
        meta = {"raw_size": int(n * arr.dtype.itemsize), "n": int(n),
                "dtype": arr.dtype.name, "block": BLOCK}
        return payload, meta

    def _encode_device(self, x) -> Tuple[Any, Dict[str, Any]]:
        import jax.numpy as jnp
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize >= 2:
            from repro.kernels.staging_pack import ops
            q, scales = ops.quantize_blocks(x, block_elems=BLOCK,
                                            impl=self.impl)
            n = int(x.size)
            # np.asarray is the device->host copy: int8 + f32 scales, not
            # the full-width floats.
            qh = np.asarray(q).reshape(-1)[:n]
            sh = np.asarray(scales).astype(np.float32, copy=False)
            payload = sh.tobytes() + qh.tobytes()
            meta = {"raw_size": int(n * np.dtype(x.dtype).itemsize),
                    "n": n, "dtype": np.dtype(x.dtype).name, "block": BLOCK}
            return payload, meta
        return self._passthrough(as_bytes_array(np.asarray(x)))

    @staticmethod
    def _passthrough(raw: np.ndarray) -> Tuple[Any, Dict[str, Any]]:
        return raw, {"raw_size": int(raw.size), "passthrough": True}

    def decode(self, payload, meta: Dict[str, Any], *,
               key: str = "") -> np.ndarray:
        raw = as_bytes_array(payload)
        if meta.get("passthrough"):
            return raw
        n = int(meta["n"])
        block = int(meta.get("block", BLOCK))
        dt = np.dtype(meta["dtype"])
        nb = -(-n // block)
        if raw.size != nb * 4 + n:
            raise ValueError(
                f"int8-block payload is {raw.size}B, expected "
                f"{nb * 4 + n}B ({nb} scales + {n} values)")
        dq = dequantize(raw[:nb * 4].view(np.float32),
                        raw[nb * 4:].view(np.int8), dt, block)
        return np.ascontiguousarray(dq).view(np.uint8)
