"""Plain reference of the seismic-hpc4e-201x501x501 configuration: the
velocity field of each step, made from the seed, and the guarantees the
configuration states about what an analyst reads back.

Step ``t`` is one trial of the HPC4e-like velocity mesh: ``n_sources``
expanding Gaussian shells of width ``width`` on the unit cube sampled at
``mesh`` points, each at radius ``velocity * (tau + 1)`` and damped by
``exp(-damping * tau)``, where the sources, amplitudes and the trial-local
time ``tau`` in [0, local_steps) are drawn from the seed and ``t``. It is
the formula of the repository's ``SeismicField`` in float32
``jax.numpy``, so a step is made on the chip in one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INT8_BLOCK = 4096          # values per scale block of the int8-block codec


def step_key(key, t):
    return jax.random.fold_in(key, t)


@functools.partial(jax.jit, static_argnames=("shape", "n_sources",
                                             "local_steps"))
def _field(key, t, *, shape, n_sources, velocity, damping, width,
           local_steps):
    k_src, k_amp, k_tau = jax.random.split(step_key(key, t), 3)
    src = jax.random.uniform(k_src, (n_sources, 3), jnp.float32, 0.1, 0.9)
    amp = jax.random.uniform(k_amp, (n_sources,), jnp.float32, 0.5, 1.5)
    tau = jax.random.randint(k_tau, (), 0, local_steps).astype(jnp.float32)
    gx, gy, gz = (jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
                  for n in shape)
    r_t = velocity * (tau + 1.0)
    out = jnp.zeros(shape, jnp.float32)
    for i in range(n_sources):
        r = jnp.sqrt((gx[:, None, None] - src[i, 0]) ** 2
                     + (gy[None, :, None] - src[i, 1]) ** 2
                     + (gz[None, None, :] - src[i, 2]) ** 2)
        shell = jnp.exp(-((r - r_t) ** 2) / (2.0 * width ** 2))
        out = out + amp[i] * jnp.exp(-damping * tau) * shell
    return out


def field(key, t: int, cfg: dict):
    """Step t of the field, float32, on the default device."""
    f = cfg["field"]
    return _field(key, jnp.uint32(t), shape=tuple(cfg["mesh"]),
                  n_sources=f["n_sources"], velocity=f["velocity"],
                  damping=f["damping"], width=f["width"],
                  local_steps=f["local_steps"])


def field_np(key, t: int, cfg: dict) -> np.ndarray:
    """The same formula in float64 numpy, from the same draws (for checking
    the float32 producer; never on the timed path)."""
    f = cfg["field"]
    k_src, k_amp, k_tau = jax.random.split(step_key(key, jnp.uint32(t)), 3)
    src = np.asarray(jax.random.uniform(k_src, (f["n_sources"], 3),
                                        jnp.float32, 0.1, 0.9), np.float64)
    amp = np.asarray(jax.random.uniform(k_amp, (f["n_sources"],),
                                        jnp.float32, 0.5, 1.5), np.float64)
    tau = float(jax.random.randint(k_tau, (), 0, f["local_steps"]))
    nx, ny, nz = cfg["mesh"]
    gx = np.linspace(0, 1, nx)[:, None, None]
    gy = np.linspace(0, 1, ny)[None, :, None]
    gz = np.linspace(0, 1, nz)[None, None, :]
    out = np.zeros((nx, ny, nz))
    r_t = f["velocity"] * (tau + 1)
    for (sx, sy, sz), a in zip(src, amp):
        r = np.sqrt((gx - sx) ** 2 + (gy - sy) ** 2 + (gz - sz) ** 2)
        out += a * np.exp(-f["damping"] * tau) * np.exp(
            -((r - r_t) ** 2) / (2 * f["width"] ** 2))
    return out


# ---------------------------------------------------------------------------
# the guarantees
# ---------------------------------------------------------------------------


def flat_index(shape, lo, hi) -> np.ndarray:
    """Flat (C-order) indices of the inclusive box [lo, hi] of `shape`."""
    idx = np.zeros((1,) * len(shape), np.int64)
    for d, (a, b) in enumerate(zip(lo, hi)):
        ax = np.arange(a, b + 1, dtype=np.int64).reshape(
            [-1 if e == d else 1 for e in range(len(shape))])
        idx = idx * shape[d] + ax
    return idx


def block_amax(step: np.ndarray, block: int = INT8_BLOCK) -> np.ndarray:
    x = np.abs(step.reshape(-1))
    pad = (-x.size) % block
    return np.pad(x, (0, pad)).reshape(-1, block).max(axis=1)


def int8_bound(amax: np.ndarray) -> np.ndarray:
    """The configuration's int8-block guarantee per block:
    |x - dq| <= scale / 2 + one float32 ulp of the block's amax, where
    scale = amax / 127 (the ulp is the rounding of the dequantised value)."""
    amax = amax.astype(np.float32)
    scale = np.where(amax > 0, amax / np.float32(127), np.float32(1))
    return scale.astype(np.float64) / 2 + np.spacing(amax).astype(np.float64)


def mismatched(got: np.ndarray, want: np.ndarray) -> int:
    """Values whose float32 bits differ (the codec=none guarantee)."""
    got = np.ascontiguousarray(got, np.float32).reshape(-1)
    want = np.ascontiguousarray(want, np.float32).reshape(-1)
    if got.size != want.size:
        return max(got.size, want.size)
    return int((got.view(np.uint32) != want.view(np.uint32)).sum())


def err_over_bound(got: np.ndarray, want: np.ndarray, flat: np.ndarray,
                   amax: np.ndarray) -> float:
    """Largest |got - want| over the int8-block bound of each value's
    block; at most 1 where the guarantee holds."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.size != want.size:
        return float("inf")
    bound = int8_bound(amax)[flat.reshape(-1) // INT8_BLOCK]
    return float((np.abs(got - want) / bound).max())


def control(want: np.ndarray, codec: str, amax: np.ndarray,
            flat: np.ndarray) -> np.ndarray:
    """The reference in the program's place at the precision below the one
    the configuration states: float32 values carried as bfloat16, or
    int8-block values quantised to 4 bits (scale = amax / 7)."""
    if codec == "none":
        return np.asarray(jnp.asarray(want).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    scale = np.where(amax > 0, amax / 7, 1).astype(np.float32)
    s = scale[flat.reshape(-1) // INT8_BLOCK].reshape(want.shape)
    return (np.clip(np.rint(want / s), -7, 7) * s).astype(np.float32)
