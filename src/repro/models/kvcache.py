"""KV / state caches for decode.

Attention caches hold absolute positions per slot so local layers can use a
ring buffer (slot = pos % window) with the same insert path as global layers.
Global-layer caches are sequence-shardable over the `data` mesh axis for
long-context decode (SP decode; see DESIGN.md §4) via the `kv_seq` logical
axis.
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


def cache_insert(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, window: int = 0) -> dict:
    """Insert one token per sequence. k_new/v_new: (B,1,Hkv,D); pos: (B,).
    Cache k/v are stored flat (B,T,Hkv*D)."""
    B = k_new.shape[0]
    T = cache["k"].shape[1]
    with jax.named_scope("kv_update"):
        b = jnp.arange(B)
        slot = pos % T
        return {
            "k": cache["k"].at[b, slot].set(k_new.reshape(B, -1)),
            "v": cache["v"].at[b, slot].set(v_new.reshape(B, -1)),
            "pos": cache["pos"].at[b, slot].set(pos),
        }


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
