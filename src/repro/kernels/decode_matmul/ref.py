"""Pure-jnp oracle for matmul_cast: cast the whole weight, then multiply
with float32 accumulation (what XLA runs for ``dense`` without the kernel)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_cast_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (M, K); w: (K, N). Returns (M, N) in x's dtype."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
