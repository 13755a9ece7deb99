"""Public model API: init / loss / prefill / decode for any ArchConfig.

Loss uses sequence-chunked cross-entropy (never materializes the full
(B,S,V) logits — V is up to 262k) with the unembed recomputed in backward
(jax.checkpoint around the chunk body).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.models.layers import (
    abstract_params, init_params, param_shardings, softcap, unembed,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # -- params -----------------------------------------------------------
    def param_specs(self) -> PyTree:
        return tfm.transformer_specs(self.cfg)

    def abstract_params(self) -> PyTree:
        return abstract_params(self.param_specs())

    def init(self, key: jax.Array) -> PyTree:
        return init_params(self.param_specs(), key)

    def param_shardings(self, mesh, rules: dict) -> PyTree:
        return param_shardings(self.param_specs(), mesh, rules)

    # -- caches -----------------------------------------------------------
    def cache_specs(self, B: int, T: int) -> PyTree:
        return tfm.cache_specs(self.cfg, B, T)

    def abstract_cache(self, B: int, T: int) -> PyTree:
        return abstract_params(self.cache_specs(B, T))

    def init_cache(self, B: int, T: int) -> PyTree:
        # zeros/neg-ones init — deterministic, key unused
        return init_params(self.cache_specs(B, T), jax.random.PRNGKey(0))

    def cache_shardings(self, B: int, T: int, mesh, rules: dict) -> PyTree:
        return param_shardings(self.cache_specs(B, T), mesh, rules)

    # -- forward ----------------------------------------------------------
    def loss_fn(self, params: PyTree, batch: dict, rules: dict,
                xent_chunk: int = 512):
        """batch: tokens/targets/loss_mask (B,S) [+ prefix_embed]. Returns
        (loss, metrics)."""
        cfg = self.cfg
        hidden, aux, _ = tfm.apply_transformer(
            params, batch["tokens"], cfg=cfg, rules=rules,
            prefix_embed=batch.get("prefix_embed"))
        if cfg.n_prefix and "prefix_embed" in batch:
            hidden = hidden[:, cfg.n_prefix:]  # loss on text positions only
        nll, z2 = _chunked_xent(params, hidden, batch["targets"],
                                batch["loss_mask"], cfg, xent_chunk)
        loss = nll + 1e-4 * z2 + 1e-2 * aux["moe_lb"] + 1e-3 * aux["moe_z"]
        metrics = {"nll": nll, "z2": z2, **aux}
        return loss, metrics

    def prefill(self, params: PyTree, tokens: jax.Array,
                rules: dict, prefix_embed: Optional[jax.Array] = None,
                max_len: int = 0):
        """Returns (last_token_logits (B,V), cache). max_len = cache
        capacity (>= prefill length; gives decode headroom)."""
        hidden, _, cache = tfm.apply_transformer(
            params, tokens, cfg=self.cfg, rules=rules,
            prefix_embed=prefix_embed, return_cache=True, cache_len=max_len)
        logits = tfm.logits_from_hidden(params, hidden[:, -1:], self.cfg)
        return logits[:, 0], cache

    def decode_step(self, params: PyTree, tokens: jax.Array, pos: jax.Array,
                    cache: PyTree, rules: dict):
        """tokens: (B,1); pos: (B,). Returns (logits (B,V), new_cache)."""
        hidden, _, new_cache = tfm.apply_transformer(
            params, tokens, cfg=self.cfg, rules=rules,
            positions=pos[:, None], cache=cache)
        logits = tfm.logits_from_hidden(params, hidden, self.cfg)
        return logits[:, 0], new_cache


def _chunked_xent(params, hidden, targets, mask, cfg, chunk: int):
    """Sequence-chunked masked cross-entropy + z-loss term.

    hidden: (B,S,M); targets/mask: (B,S). Unembed is recomputed in backward.
    """
    B, S, M = hidden.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (S + pad) // c
    hc = jnp.moveaxis(hidden.reshape(B, n, c, M), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n, c), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def chunk_fn(carry, xs):
        h, t, m = xs
        lg = unembed(params["embed"], h, cfg.tie_embeddings)
        lg = softcap(lg, cfg.logit_softcap).astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)                      # (B,c)
        # one-hot contraction, not take_along_axis: a gather along the
        # model-sharded vocab axis aborts XLA's SPMD partitioner inside
        # the pod-manual shard_map of the compressed train step
        tgt = jnp.sum(lg * jax.nn.one_hot(t, lg.shape[-1], dtype=lg.dtype),
                      axis=-1)
        nll_sum, z2_sum, m_sum = carry
        nll_sum = nll_sum + jnp.sum((logz - tgt) * m)
        z2_sum = z2_sum + jnp.sum(jnp.square(logz) * m)
        return (nll_sum, z2_sum, m_sum + jnp.sum(m)), None

    (nll_sum, z2_sum, m_sum), _ = jax.lax.scan(
        chunk_fn, (jnp.float32(0), jnp.float32(0), jnp.float32(0)),
        (hc, tc, mc))
    denom = jnp.maximum(m_sum, 1.0)
    return nll_sum / denom, z2_sum / denom


@functools.lru_cache(maxsize=None)
def get_model(arch: str) -> Model:
    from repro.configs import get_config
    return Model(get_config(arch))
