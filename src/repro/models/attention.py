"""Attention: GQA with RoPE, chunked-flash prefill, banded local attention,
ring-buffer local KV cache, sequence-shardable global KV cache (SP decode).

Impl-switchable: the XLA path here is what the dry-run lowers; the Pallas
flash kernel (repro/kernels/flash_attention) is the TPU drop-in selected via
``impl="pallas"`` in ops dispatch.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (
    ParamSpec, constrain, matmul, prefer_dtype, rms_norm, rope, softcap,
)

NEG_INF = -2.0e38  # fp32-safe large negative (avoid nan from inf-inf)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_specs(cfg) -> dict[str, ParamSpec]:
    """Projections are stored FLATTENED (M, H*D): the flattened width is
    divisible by the 16-way model axis for every assigned arch even when
    the head count is not (gemma3/paligemma: 8 heads) — GSPMD re-factorizes
    the (H, D) reshape, so attention TP always shards."""
    M, Hq, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pdt = jnp.dtype(cfg.param_dtype)
    specs = {
        "wq": ParamSpec((M, Hq * D), ("embed", "qkv"), pdt),
        "wk": ParamSpec((M, Hkv * D), ("embed", "kv_flat"), pdt),
        "wv": ParamSpec((M, Hkv * D), ("embed", "kv_flat"), pdt),
        "wo": ParamSpec((Hq * D, M), ("qkv", "embed"), pdt),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((Hq * D,), ("qkv",), pdt, init="zeros")
        specs["bk"] = ParamSpec((Hkv * D,), ("kv_flat",), pdt, init="zeros")
        specs["bv"] = ParamSpec((Hkv * D,), ("kv_flat",), pdt, init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((D,), ("head_dim",), init="ones")
        specs["k_norm"] = ParamSpec((D,), ("head_dim",), init="ones")
    return specs


# ---------------------------------------------------------------------------
# Mask predicates (absolute positions)
# ---------------------------------------------------------------------------


def make_mask_fn(*, causal: bool, window: int, prefix: int) -> Callable:
    """Returns mask_fn(q_pos (Q,), k_pos (K,)) -> bool (Q, K)."""

    def mask_fn(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
        q_pos, k_pos = q_pos[:, None], k_pos[None, :]
        ok = k_pos <= q_pos if causal else jnp.ones_like(q_pos == k_pos)
        if window:
            ok &= (q_pos - k_pos) < window
        if prefix:
            ok |= k_pos < prefix  # prefix-LM: everything sees the prefix
        return ok

    return mask_fn


# ---------------------------------------------------------------------------
# Core attends
# ---------------------------------------------------------------------------


def _attend_dense(q, k, v, q_pos, k_pos, mask_fn, scale, cap):
    """q: (B,Q,Hk,G,D); k/v: (B,K,Hk,D). fp32 softmax. Returns (B,Q,Hk,G,D)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = softcap(s * scale, cap)
    mask = mask_fn(q_pos, k_pos)  # (Q, K)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(jnp.maximum(m, NEG_INF / 2)))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def flash_attention_xla(q, k, v, *, mask_fn, scale, cap, chunk_q, chunk_k,
                        q_offset=0):
    """Memory-efficient chunked attention (online softmax), lax.map over query
    chunks + lax.scan over kv chunks. q: (B,Sq,Hk,G,D); k/v: (B,Sk,Hk,D)."""
    B, Sq, Hk, G, D = q.shape
    Sk = k.shape[1]
    cq, ck = min(chunk_q, Sq), min(chunk_k, Sk)
    if Sq % cq or Sk % ck:  # pad; padded kv slots are masked via kv_len
        pq, pk = (-Sq) % cq, (-Sk) % ck
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        inner = functools.partial(
            flash_attention_xla, mask_fn=lambda qp, kp: mask_fn(qp, kp)
            & (kp < Sk)[None, :], scale=scale, cap=cap, chunk_q=cq,
            chunk_k=ck, q_offset=q_offset)
        return inner(q, k, v)[:, :Sq]
    nq, nk = Sq // cq, Sk // ck
    if nq == 1 and nk == 1:
        qp = q_offset + jnp.arange(Sq)
        return _attend_dense(q, k, v, qp, jnp.arange(Sk), mask_fn, scale, cap)

    qc = jnp.moveaxis(q.reshape(B, nq, cq, Hk, G, D), 1, 0)      # (nq,B,cq,Hk,G,D)
    kc = jnp.moveaxis(k.reshape(B, nk, ck, Hk, D), 1, 0)         # (nk,B,ck,Hk,D)
    vc = jnp.moveaxis(v.reshape(B, nk, ck, Hk, D), 1, 0)

    @jax.checkpoint  # flash backward = recompute; never save p/scores
    def per_q(args):
        qi, qb = args
        q_pos = q_offset + qi * cq + jnp.arange(cq)

        def body(carry, kin):
            ki, kb, vb = kin
            m, l, acc = carry
            k_pos = ki * ck + jnp.arange(ck)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32)
            s = softcap(s * scale, cap)
            s = jnp.where(mask_fn(q_pos, k_pos)[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l, acc), None

        init = (
            jnp.full((B, Hk, G, cq), NEG_INF, jnp.float32),
            jnp.zeros((B, Hk, G, cq), jnp.float32),
            jnp.zeros((B, Hk, G, cq, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(body, init, (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)            # (B,cq,Hk,G,D)

    outs = jax.lax.map(per_q, (jnp.arange(nq), qc))               # (nq,B,cq,...)
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hk, G, D)


def local_attention_xla(q, k, v, *, window, scale, cap, q_offset=0):
    """Banded sliding-window attention: queries in chunks of `window`, each
    attending the previous+current kv chunk only → O(S·2w) FLOPs (honest
    sub-quadratic cost in HLO). q: (B,S,Hk,G,D); k/v: (B,S,Hk,D)."""
    B, S, Hk, G, D = q.shape
    w = min(window, S)
    pad = (-S) % w
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    Sp = S + pad
    n = Sp // w
    qc = jnp.moveaxis(qp.reshape(B, n, w, Hk, G, D), 1, 0)        # (n,B,w,...)

    def windows(x):  # (B,Sp,Hk,D) -> (n,B,2w,Hk,D): [prev chunk | this chunk]
        xpad = jnp.pad(x, ((0, 0), (w, 0), (0, 0), (0, 0)))
        xc = xpad.reshape(B, n + 1, w, *x.shape[2:])
        return jnp.moveaxis(jnp.concatenate([xc[:, :-1], xc[:, 1:]], axis=2), 1, 0)

    kw, vw = windows(kp), windows(vp)
    base_mask = make_mask_fn(causal=True, window=w, prefix=0)

    def mask_fn(q_pos, k_pos):  # exclude the padded leading chunk (pos < 0)
        return base_mask(q_pos, k_pos) & (k_pos >= q_offset)[None, :]

    @jax.checkpoint  # never save the banded scores for backward
    def per_chunk(args):
        i, qb, kb, vb = args
        q_pos = q_offset + i * w + jnp.arange(w)
        k_pos = q_offset + (i - 1) * w + jnp.arange(2 * w)        # may be negative -> masked
        return _attend_dense(qb, kb, vb, q_pos, k_pos, mask_fn, scale, cap)

    outs = jax.lax.map(per_chunk, (jnp.arange(n), qc, kw, vw))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sp, Hk, G, D)
    return out[:, :S]


def decode_attention_xla(q, k_cache, v_cache, *, pos, cache_positions, scale,
                         cap, window=0):
    """One-token decode. q: (B,1,Hk,G,D); caches: (B,T,Hk,D);
    pos: (B,) absolute position of the new token;
    cache_positions: (B,T) absolute position stored in each cache slot
    (ring buffers make slot order != position order). Invalid slots < 0."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k_cache,
                   preferred_element_type=jnp.float32)
    s = softcap(s * scale, cap)
    valid = (cache_positions >= 0) & (cache_positions <= pos[:, None])
    if window:
        valid &= (pos[:, None] - cache_positions) < window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    # stable softmax over the cache axis (sharded over `data` in long_500k —
    # GSPMD inserts the all-reduce for these reductions: SP decode)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + dispatch + cache handling)
# ---------------------------------------------------------------------------


def attention(params: dict, x: jax.Array, *, cfg, rules: dict, kind: str,
              positions: jax.Array, cache: Optional[dict] = None,
              return_cache: bool = False, cache_len: int = 0,
              layer: Optional[jax.Array] = None):
    """kind: dense|global|local. x: (B,S,M). positions: (B,S) absolute.

    Modes:
      * train/prefill: cache is None; returns (y, new_cache|None)
      * decode:        cache is dict;  returns (y, updated_cache). With
        `layer`, cache is the scan's stacked cache, this layer's row is
        updated in place and the whole stack returned.
    """
    B, S, M = x.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = Hq // Hkv
    scale = cfg.query_scale or D ** -0.5
    window = cfg.attn_window if kind == "local" else 0
    theta = cfg.rope_theta if kind != "local" else min(cfg.rope_theta, 10_000.0)

    q = matmul(x, params["wq"])
    k = matmul(x, params["wk"])
    v = matmul(x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    decode = cache is not None and S == 1
    if decode:
        # decode: new-token q/k/v are tiny; pin them to the CACHE layout
        # (batch x kv_heads) so GSPMD reshards the token, not the cache
        q = constrain(q.reshape(B, S, Hq, D), rules,
                      "batch", None, "heads", "head_dim")
        k = constrain(k.reshape(B, S, Hkv, D), rules,
                      "batch", None, "kv_heads", "head_dim")
        v = constrain(v.reshape(B, S, Hkv, D), rules,
                      "batch", None, "kv_heads", "head_dim")
    else:
        q = constrain(q, rules, "batch", None, "qkv").reshape(B, S, Hq, D)
        k = constrain(k, rules, "batch", None, "kv_flat").reshape(B, S, Hkv, D)
        v = constrain(v, rules, "batch", None, "kv_flat").reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    qg = q.reshape(B, S, Hkv, G, D)

    # padded-MHA mode (train/prefill): when the head count doesn't divide
    # the TP axis, GSPMD splits mid-head and all-reduces SCORES. Instead:
    # pad q per kv-group to Hp (divisible), repeat kv, run scores in MHA
    # layout (per-head local), slice the inert pad heads off before wo —
    # mathematically exact (padded outputs are discarded).
    pad_mha = cfg.pad_heads_to > Hq and not (cache is not None and S == 1)
    if pad_mha:
        Gp = cfg.pad_heads_to // Hkv
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
        qg = qg.reshape(B, S, cfg.pad_heads_to, 1, D)
        qg = constrain(qg, rules, "batch", None, "heads_padded", None, None)
        k_att = jnp.repeat(k, Gp, axis=2)        # (B,S,Hp,D)
        v_att = jnp.repeat(v, Gp, axis=2)
        k_att = constrain(k_att, rules, "batch", None, "heads_padded", None)
        v_att = constrain(v_att, rules, "batch", None, "heads_padded", None)
    else:
        k_att, v_att = k, v

    if decode:  # one-token decode against the cache
        from repro.models.kvcache import cache_insert  # local: no cycle
        new_cache, own = cache_insert(cache, k, v, positions[:, 0],
                                      layer=layer)
        T = own["k"].shape[1]
        kc = own["k"].reshape(B, T, Hkv, D)
        vc = own["v"].reshape(B, T, Hkv, D)
        o = decode_attention_xla(
            qg, kc, vc, pos=positions[:, 0],
            cache_positions=own["pos"], scale=scale, cap=cfg.attn_softcap,
            window=window)
    else:  # train / prefill
        if kind == "local":
            o = local_attention_xla(qg, k_att, v_att, window=cfg.attn_window,
                                    scale=scale, cap=cfg.attn_softcap)
        else:
            mask_fn = make_mask_fn(causal=True, window=0, prefix=cfg.n_prefix
                                   if cfg.prefix_bidirectional else 0)
            o = flash_attention_xla(qg, k_att, v_att, mask_fn=mask_fn,
                                    scale=scale, cap=cfg.attn_softcap,
                                    chunk_q=cfg.attn_chunk,
                                    chunk_k=cfg.attn_chunk)
        if pad_mha:  # drop the inert pad heads: o (B,S,Hp,1,D)->(B,S,Hkv,G,D)
            o = o.reshape(B, S, Hkv, Gp, D)[:, :, :, :G]
        new_cache = None
        if return_cache:
            from repro.models.kvcache import cache_from_prefill
            new_cache = cache_from_prefill(k, v, positions,
                                           window=cfg.attn_window
                                           if kind == "local" else 0,
                                           max_len=cache_len)

    o = o.reshape(B, S, Hq * D)
    o = constrain(o, rules, "batch", None, "qkv")
    y = matmul(o, params["wo"], prefer_dtype(x.dtype))
    return constrain(y, rules, "batch", None, None), new_cache
