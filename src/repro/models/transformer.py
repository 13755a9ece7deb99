"""Decoder-stack assembly for all 10 assigned architectures.

Heterogeneous layer stacks (gemma2 LG, gemma3 LLLLLG, recurrentgemma RRA) are
scanned over *periods*: the scan body unrolls one period of distinct layer
kinds, the scan runs n_layers // period times, remainder layers run unrolled.
This keeps HLO size ~constant in depth (critical for the 80-compile dry-run)
and bounds live activations to one period (+remat policy).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import kvcache, moe as moe_lib, rglru as rglru_lib, ssm as ssm_lib
from repro.models.layers import (
    ParamSpec, constrain, decode_params, embed, embed_specs, mlp, mlp_specs,
    param_logical_axes, rms_norm, rms_norm_spec, softcap, stack_specs,
    unembed,
)

AUX0 = {"moe_lb": 0.0, "moe_z": 0.0}


def _key(i: int, kind: str) -> str:
    return f"{i}:{kind}"


def _plan(cfg) -> tuple[int, int]:
    """(n_scan_periods, n_remainder_layers)."""
    p = len(cfg.layer_pattern)
    n_scan = cfg.n_layers // p if cfg.scan_layers else 0
    if n_scan < 2:
        n_scan = 0
    return n_scan, cfg.n_layers - n_scan * p


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def block_specs(cfg, kind: str) -> dict:
    pdt = jnp.dtype(cfg.param_dtype)
    plus = cfg.scale_embeddings  # gemma-family (1+w) norm convention
    s: dict[str, Any] = {"ln1": rms_norm_spec(cfg.d_model, plus)}
    if kind in ("dense", "global", "local", "moe"):
        s["attn"] = attn_lib.attn_specs(cfg)
        s["ln2"] = rms_norm_spec(cfg.d_model, plus)
        if kind == "moe":
            s["moe"] = moe_lib.moe_specs(cfg)
        else:
            s["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_glu, pdt)
        if cfg.post_norms:
            s["ln1_post"] = rms_norm_spec(cfg.d_model, plus)
            s["ln2_post"] = rms_norm_spec(cfg.d_model, plus)
    elif kind == "mamba":
        s["mamba"] = ssm_lib.mamba_specs(cfg)
    elif kind == "rglru":
        s["rglru"] = rglru_lib.rglru_specs(cfg)
        s["ln2"] = rms_norm_spec(cfg.d_model, plus)
        s["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_glu, pdt)
    else:
        raise ValueError(kind)
    return s


def transformer_specs(cfg) -> dict:
    n_scan, n_rem = _plan(cfg)
    pat = cfg.layer_pattern
    specs: dict[str, Any] = {
        "embed": embed_specs(cfg.vocab_size, cfg.d_model, cfg.tie_embeddings,
                             jnp.dtype(cfg.param_dtype)),
        "final_ln": rms_norm_spec(cfg.d_model, cfg.scale_embeddings),
        "scan": {_key(i, k): stack_specs(block_specs(cfg, k), n_scan)
                 for i, k in enumerate(pat)} if n_scan else {},
        "rem": {_key(j, pat[j % len(pat)]): block_specs(cfg, pat[j % len(pat)])
                for j in range(n_rem)},
    }
    return specs


def cache_specs(cfg, B: int, T: int) -> dict:
    n_scan, n_rem = _plan(cfg)
    pat = cfg.layer_pattern

    def layer(kind):
        return kvcache.layer_cache_specs(cfg, kind, B, T)

    return {
        "scan": {_key(i, k): stack_specs(layer(k), n_scan)
                 for i, k in enumerate(pat)} if n_scan else {},
        "rem": {_key(j, pat[j % len(pat)]): layer(pat[j % len(pat)])
                for j in range(n_rem)},
    }


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def apply_block(kind: str, p: dict, x: jax.Array, aux: dict, *, cfg,
                rules: dict, positions: jax.Array,
                cache: Optional[dict], return_cache: bool,
                cache_len: int = 0, layer: Optional[jax.Array] = None):
    """One layer. With `layer`, `cache` is the scan's stacked cache of this
    kind and comes back with row `layer` updated (kvcache)."""
    from jax.ad_checkpoint import checkpoint_name as name
    eps, plus = cfg.norm_eps, cfg.scale_embeddings
    new_cache = None
    if kind in ("dense", "global", "local", "moe"):
        h = rms_norm(x, p["ln1"], eps, plus)
        with jax.named_scope("attn"):
            a_out, new_cache = attn_lib.attention(
                p["attn"], h, cfg=cfg, rules=rules,
                kind="global" if kind == "moe" else kind,
                positions=positions, cache=cache,
                return_cache=return_cache, cache_len=cache_len, layer=layer)
        a_out = name(a_out, "attn_out")
        if cfg.post_norms:
            a_out = rms_norm(a_out, p["ln1_post"], eps, plus)
        x = x + a_out
        h2 = rms_norm(x, p["ln2"], eps, plus)
        if kind == "moe":
            f_out, moe_aux = moe_lib.moe_block(p["moe"], h2, cfg=cfg, rules=rules)
            aux = {k: aux[k] + moe_aux.get(k, 0.0) for k in aux}
        else:
            with jax.named_scope("mlp"):
                f_out = mlp(p["mlp"], h2, cfg.mlp_act, rules)
        f_out = name(f_out, "ffn_out")
        if cfg.post_norms:
            f_out = rms_norm(f_out, p["ln2_post"], eps, plus)
        x = x + f_out
    elif kind == "mamba":
        h = rms_norm(x, p["ln1"], eps, plus)
        out, new_cache = ssm_lib.mamba_block(
            p["mamba"], h, cfg=cfg, rules=rules,
            cache=kvcache.layer_of(cache, layer), return_cache=return_cache)
        new_cache = kvcache.state_replace(cache, new_cache, layer)
        x = x + name(out, "mixer_out")
    elif kind == "rglru":
        h = rms_norm(x, p["ln1"], eps, plus)
        out, new_cache = rglru_lib.rglru_block(
            p["rglru"], h, cfg=cfg, rules=rules,
            cache=kvcache.layer_of(cache, layer), return_cache=return_cache)
        new_cache = kvcache.state_replace(cache, new_cache, layer)
        x = x + name(out, "mixer_out")
        h2 = rms_norm(x, p["ln2"], eps, plus)
        x = x + name(mlp(p["mlp"], h2, cfg.mlp_act, rules), "ffn_out")
    else:
        raise ValueError(kind)
    return constrain(x, rules, "batch", None, None), aux, new_cache


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    if cfg.remat == "comm":
        # save the post-all-reduce sublayer outputs: backward recompute
        # stops at them, so the forward TP all-reduces are NOT re-issued
        # in the backward pass (§Perf hillclimb; costs one extra saved
        # (B,S,M) tensor per sublayer)
        pol = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out", "mixer_out")
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def apply_stack(params: dict, x: jax.Array, *, cfg, rules: dict,
                positions: jax.Array, cache: Optional[dict] = None,
                return_cache: bool = False, cache_len: int = 0):
    """Runs all layers. Returns (x, aux, new_cache|None).

    Scanned layers' caches are stacked on a leading `layers` axis. Prefill
    (`return_cache`, no input cache) builds them as the scan's outputs.
    Decode (an input cache) carries them through the scan and updates each
    layer's row in place (kvcache), so a step writes only its new tokens;
    its attention and MLP matrices reach the layer unsliced
    (`decode_params`), so a weight stored wider than the compute dtype is
    read once, in place, by the matmul that uses it.
    """
    pat = cfg.layer_pattern
    n_scan, n_rem = _plan(cfg)
    aux = dict(AUX0)
    new_cache: dict[str, Any] = {"scan": {}, "rem": {}}
    use_cache = cache is not None

    if n_scan:
        # remat at BLOCK granularity: the scan saves only the carry per
        # period; backward recomputes one block at a time (working set =
        # one layer, not one period)
        def block_fn(kind, p, xc, auxc, c_in, layer=None):
            return apply_block(
                kind, p, xc, auxc, cfg=cfg, rules=rules,
                positions=positions, cache=c_in, return_cache=return_cache,
                cache_len=cache_len, layer=layer)

        if use_cache:
            # pin the carried caches to their stacked layout so GSPMD does
            # not reshard them at the loop boundary
            axes = param_logical_axes(cache_specs(cfg, 1, 1)["scan"])

            def pin(c):
                return jax.tree.map(lambda ax, a: constrain(a, rules, *ax),
                                    axes, c,
                                    is_leaf=lambda t: isinstance(t, tuple))

            def decode_body(carry, layer):
                xc, auxc, cc = carry
                cc = dict(cc)
                for i, kind in enumerate(pat):
                    key = _key(i, kind)
                    fn = _remat(cfg, functools.partial(block_fn, kind))
                    xc, auxc, cc[key] = fn(
                        decode_params(params["scan"][key], layer), xc, auxc,
                        cc[key], layer)
                return (xc, auxc, pin(cc)), None

            (x, aux, new_cache["scan"]), _ = jax.lax.scan(
                decode_body, (x, aux, pin(cache["scan"])), jnp.arange(n_scan))
        else:
            def body(carry, p_period):
                xc, auxc = carry
                outs = {}
                for i, kind in enumerate(pat):
                    key = _key(i, kind)
                    fn = _remat(cfg, functools.partial(block_fn, kind))
                    xc, auxc, nc = fn(p_period[key], xc, auxc, None)
                    if nc is not None:
                        outs[key] = nc
                return (xc, auxc), (outs if outs else 0.0)

            (x, aux), ys = jax.lax.scan(body, (x, aux), params["scan"])
            if return_cache:
                new_cache["scan"] = ys

    for j in range(n_rem):
        kind = pat[j % len(pat)]
        key = _key(j, kind)
        c_in = cache["rem"][key] if use_cache else None

        def one(carry, p, kind=kind, c_in=c_in):
            xc, auxc = carry
            return apply_block(kind, p, xc, auxc, cfg=cfg, rules=rules,
                               positions=positions, cache=c_in,
                               return_cache=return_cache,
                               cache_len=cache_len)  # rematted below

        p = params["rem"][key]
        xr, aux, nc = _remat(cfg, one)(
            (x, aux), decode_params(p) if use_cache else p)
        x = xr
        if nc is not None:
            new_cache["rem"][key] = nc

    out_cache = new_cache if (use_cache or return_cache) else None
    return x, aux, out_cache


def apply_transformer(params: dict, tokens: jax.Array, *, cfg, rules: dict,
                      positions: Optional[jax.Array] = None,
                      prefix_embed: Optional[jax.Array] = None,
                      cache: Optional[dict] = None,
                      return_cache: bool = False, cache_len: int = 0):
    """Returns (hidden (B,S_total,M), aux, new_cache). Logits are computed by
    the caller (chunked xent for train; last-token unembed for prefill)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = embed(params["embed"], tokens, cfg.scale_embeddings, cdt)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(cdt), x], axis=1)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, rules, "batch", None, None)
    x, aux, new_cache = apply_stack(
        params, x, cfg=cfg, rules=rules, positions=positions, cache=cache,
        return_cache=return_cache, cache_len=cache_len)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.scale_embeddings)
    return x, aux, new_cache


def logits_from_hidden(params: dict, hidden: jax.Array, cfg,
                       rules: Optional[dict] = None) -> jax.Array:
    with jax.named_scope("head"):
        lg = unembed(params["embed"], hidden, cfg.tie_embeddings)
    if rules is not None:
        lg = constrain(lg, rules, "batch", None, "vocab")
    return softcap(lg, cfg.logit_softcap)
