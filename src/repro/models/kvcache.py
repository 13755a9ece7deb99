"""KV / state caches for decode.

Attention caches hold absolute positions per slot so local layers can use a
ring buffer (slot = pos % window) with the same insert path as global layers.
Global-layer caches are sequence-shardable over the `data` mesh axis for
long-context decode (SP decode; see DESIGN.md §4) via the `kv_seq` logical
axis.

Layers under the layer scan keep their caches stacked on a leading `layers`
axis. Prefill builds them as the scan's outputs. Decode carries the stacked
caches through the scan and updates them in place: each layer reads its row
with a dynamic index (`layer_of`), an attention layer scatters its new token
into `[layer, b, pos % T]` (`cache_insert`), and a recurrent layer replaces
its row whole (`state_replace`). Each op takes `layer=None` for the cache of
one unstacked layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec

# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def attn_cache_specs(cfg, B: int, T: int, kind: str) -> dict[str, ParamSpec]:
    """kind local -> ring buffer of size window; else full T.

    k/v are stored FLATTENED (B, T, Hkv*D) on the `kv_flat` logical axis —
    divisible by the 16-way model axis for every assigned arch (unlike the
    head count), so caches always TP-shard (incl. MQA) and match the
    in-loop sharding GSPMD picks (no loop-boundary cache gathers)."""
    size = min(cfg.attn_window, T) if kind == "local" else T
    cdt = jnp.dtype(cfg.compute_dtype)
    seq_ax = "kv_seq" if kind != "local" else None  # rings are small
    F = cfg.n_kv_heads * cfg.head_dim
    return {
        "k": ParamSpec((B, size, F), ("batch", seq_ax, "kv_flat"), cdt,
                       init="zeros"),
        "v": ParamSpec((B, size, F), ("batch", seq_ax, "kv_flat"), cdt,
                       init="zeros"),
        "pos": ParamSpec((B, size), ("batch", seq_ax), jnp.int32, init="neg_ones"),
    }


def mamba_cache_specs(cfg, B: int) -> dict[str, ParamSpec]:
    s, di = cfg.ssm, cfg.d_inner
    return {
        "conv": ParamSpec((B, s.d_conv - 1, di), ("batch", None, "inner"),
                          jnp.dtype(cfg.compute_dtype), init="zeros"),
        "h": ParamSpec((B, di, s.d_state), ("batch", "inner", "state"),
                       jnp.float32, init="zeros"),
    }


def rglru_cache_specs(cfg, B: int) -> dict[str, ParamSpec]:
    dr = cfg.d_rnn
    return {
        "conv": ParamSpec((B, cfg.rglru.d_conv - 1, dr), ("batch", None, "rnn"),
                          jnp.dtype(cfg.compute_dtype), init="zeros"),
        "h": ParamSpec((B, dr), ("batch", "rnn"), jnp.float32, init="zeros"),
    }


def layer_cache_specs(cfg, kind: str, B: int, T: int) -> Optional[dict]:
    if kind in ("dense", "global", "local", "moe"):
        return attn_cache_specs(cfg, B, T, kind)
    if kind == "mamba":
        return mamba_cache_specs(cfg, B)
    if kind == "rglru":
        return rglru_cache_specs(cfg, B)
    return None


# ---------------------------------------------------------------------------
# Attention-cache ops
# ---------------------------------------------------------------------------


def layer_of(cache: Optional[dict], layer: Optional[jax.Array]):
    """The cache of one layer: row `layer` of a stacked cache, or `cache`
    itself when `layer` is None."""
    if layer is None:
        return cache
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        cache)


def cache_insert(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, layer: Optional[jax.Array] = None
                 ) -> tuple[dict, dict]:
    """Insert one token per sequence. k_new/v_new: (B,1,Hkv,D); pos: (B,).
    Cache k/v are stored flat (B,T,Hkv*D), or (L,B,T,Hkv*D) stacked, in which
    case the token goes to row `layer` in place: one scatter of B tokens.

    Returns (cache, the layer's own cache), both holding the token. For a
    stacked cache the layer's own is its row read before the insert, with
    the token scattered into it too: the attention reads that copy, since
    reading the row back out of the updated stack costs 4 ms more of a
    musicgen-medium decode step at 16 x 750 tokens on one TPU v5e."""
    B = k_new.shape[0]
    T = cache["k"].shape[-2]
    new = {"k": k_new.reshape(B, -1), "v": v_new.reshape(B, -1), "pos": pos}

    def put(c, idx):
        return {name: c[name].at[idx].set(new[name]) for name in new}

    with jax.named_scope("kv_update"):
        rows = (jnp.arange(B), pos % T)
        if layer is None:
            own = put(cache, rows)
            return own, own
        return put(cache, (layer,) + rows), put(layer_of(cache, layer), rows)


def state_replace(cache: Optional[dict], new: Optional[dict],
                  layer: Optional[jax.Array] = None) -> Optional[dict]:
    """Recurrent state (conv, h) is replaced whole every step: `new` is the
    layer's cache, or is written into row `layer` of the stacked `cache`."""
    if layer is None:
        return new
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
        cache, new)


def cache_from_prefill(k: jax.Array, v: jax.Array, positions: jax.Array,
                       window: int = 0, max_len: int = 0) -> dict:
    """Build a cache from prefill-computed k/v (B,S,Hkv,D), rope applied.

    Global: the cache IS the kv sequence, padded to `max_len` capacity so
    subsequent decode inserts don't evict (slots beyond S hold pos=-1).
    Local: keep the last `window` entries, scattered to their ring slots
    (slot = pos % window; rings wrap by design). Stored flat (B,T,Hkv*D).
    """
    B, S = k.shape[:2]
    k = k.reshape(B, S, -1)
    v = v.reshape(B, S, -1)
    if not window or S <= window:
        if window and S < window:  # pad ring to full window size
            pad = window - S
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
            positions = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
        if window:  # scatter to ring slots
            return _scatter_ring(k, v, positions, window)
        if max_len and max_len > S:  # global: headroom for decode
            pad = max_len - S
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
            positions = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
        return {"k": k, "v": v, "pos": positions}
    return _scatter_ring(k[:, -window:], v[:, -window:], positions[:, -window:],
                         window)


def _scatter_ring(k, v, positions, window):
    B = k.shape[0]
    slots = jnp.where(positions >= 0, positions % window, 0)
    b = jnp.arange(B)[:, None]
    return {
        "k": jnp.zeros_like(k).at[b, slots].set(k),
        "v": jnp.zeros_like(v).at[b, slots].set(v),
        "pos": jnp.full_like(positions, -1).at[b, slots].set(positions),
    }
