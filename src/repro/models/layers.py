"""Parameter machinery + shared layers (pure-pytree, no flax).

Every parameter is declared once as a ``ParamSpec`` carrying shape, dtype,
logical axis names and an initializer. From the same spec tree we derive:
  * materialized params         (init_params)
  * ShapeDtypeStruct stand-ins  (abstract_params — dry-run, no allocation)
  * NamedShardings              (param_shardings via logical->mesh rules)

Logical axis vocabulary (see rules in train/sharding.py):
  batch seq embed vocab heads kv_heads head_dim qkv ffn
  expert capacity rnn inner state conv dt layers
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_matmul import ops as decode_matmul_ops

PyTree = Any

# ---------------------------------------------------------------------------
# ParamSpec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical_axes: tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"   # normal | zeros | ones | conv | a_log
    scale: float = 1.0     # fan-in style scale multiplier for "normal"
    # zero the tail of one axis (inert padded attention heads):
    zero_from: Optional[tuple[int, int]] = None   # (axis, start_index)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "neg_ones":
            return jnp.full(self.shape, -1, self.dtype)
        if self.init == "a_log":  # mamba A init: log(1..d_state) per channel
            d_state = self.shape[-1]
            a = jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32), self.shape[:-1] + (1,))
            return jnp.log(a).astype(self.dtype)
        # truncated-normal, fan-in scaled; a stacked `layers` axis is not
        # part of the fan-in
        dims = self.shape[1:] if self.logical_axes[:1] == ("layers",) \
            else self.shape
        fan_in = dims[0] if len(dims) >= 2 else max(dims[-1], 1)
        std = self.scale / np.sqrt(fan_in)
        arr = (std * jax.random.truncated_normal(
            key, -2.0, 2.0, self.shape)).astype(self.dtype)
        if self.zero_from is not None:
            ax, start = self.zero_from
            idx = [slice(None)] * len(self.shape)
            idx[ax] = slice(start, None)
            arr = arr.at[tuple(idx)].set(0)   # inert padded heads stay 0
        return arr

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(spec_tree: PyTree, key: jax.Array) -> PyTree:
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [s.materialize(k) for s, k in zip(leaves, keys)])


def abstract_params(spec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.abstract(), spec_tree, is_leaf=is_spec)


def param_logical_axes(spec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.logical_axes, spec_tree, is_leaf=is_spec)


def stack_specs(spec_tree: PyTree, n: int) -> PyTree:
    """Prepend a scanned `layers` axis of length n to every spec."""
    def f(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(
            s, shape=(n, *s.shape), logical_axes=("layers", *s.logical_axes)
        )
    return jax.tree.map(f, spec_tree, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Logical-axis sharding
# ---------------------------------------------------------------------------


def logical_to_pspec(axes: Sequence[Optional[str]], rules: dict[str, Any]) -> jax.sharding.PartitionSpec:
    return jax.sharding.PartitionSpec(*[rules.get(a) if a else None for a in axes])


def param_shardings(spec_tree: PyTree, mesh, rules: dict[str, Any]) -> PyTree:
    def f(s: ParamSpec):
        return jax.sharding.NamedSharding(mesh, logical_to_pspec(s.logical_axes, rules))
    return jax.tree.map(f, spec_tree, is_leaf=is_spec)


def constrain(x: jax.Array, rules: dict[str, Any], *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axis names (no-op outside a mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, logical_to_pspec(axes, rules))
    except (ValueError, RuntimeError):
        return x  # no mesh context (pure-CPU smoke path)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    s = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(jnp.float32)
    return (y * s).astype(dt)


def rms_norm_spec(dim: int, plus_one: bool = False) -> ParamSpec:
    return ParamSpec((dim,), ("embed",), init="zeros" if plus_one else "ones")


_LOWP_COLLECTIVES = False  # set via lowp_collectives(); read at trace time


def lowp_collectives(enabled: bool = True):
    """Context manager: emit TP-contraction outputs in the compute dtype so
    GSPMD's partial-sum all-reduces ride the wire in bf16 instead of the
    dot's f32 accumulator (per-shard accumulation stays f32 inside the MXU;
    only the cross-shard reduction is bf16 — standard Megatron practice).
    Halves the dominant collective bytes (§Perf hillclimb)."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        global _LOWP_COLLECTIVES
        prev = _LOWP_COLLECTIVES
        _LOWP_COLLECTIVES = enabled
        try:
            yield
        finally:
            _LOWP_COLLECTIVES = prev

    return _ctx()


def prefer_dtype(dt):
    return dt if _LOWP_COLLECTIVES else None


def cast_weight(w: jax.Array, dtype) -> jax.Array:
    """A stored weight in the compute dtype; the cast is named
    ``weight_cast`` in the compiled program's op metadata."""
    with jax.named_scope("weight_cast"):
        return w.astype(dtype)


#: most activation rows for which a decode matmul reads a weight stored in
#: another dtype in place (the decode_matmul kernel) instead of casting it
#: first: up to here the product is bound by the weight's bytes
DECODE_ROWS = 128

#: sub-blocks whose matrices decode reads through `matmul`
_DECODE_MATMUL_BLOCKS = ("attn", "mlp")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("stack", "layer"), meta_fields=("name",))
@dataclasses.dataclass(frozen=True)
class LayerWeight:
    """A decode step's weight matrix, left unsliced: row `layer` of the
    stacked `stack` (L, K, N), or `stack` itself (K, N) when `layer` is
    None. `name` names the kernel that reads it in the device trace."""
    stack: jax.Array
    layer: Optional[jax.Array]
    name: str

    def row(self) -> jax.Array:
        if self.layer is None:
            return self.stack
        return jax.lax.dynamic_index_in_dim(self.stack, self.layer,
                                            keepdims=False)


def decode_params(block: dict, layer: Optional[jax.Array] = None) -> dict:
    """One layer's parameters for a decode step, from a block's parameters
    (stacked on a leading layers axis when `layer` is given). Attention and
    MLP matrices stay whole as `LayerWeight`s, so `matmul` can read them in
    place; every other leaf is sliced."""
    def leaf(path, a):
        keys = [getattr(k, "key", None) for k in path]
        if (len(keys) >= 2 and keys[-2] in _DECODE_MATMUL_BLOCKS
                and a.ndim == (2 if layer is None else 3)):
            return LayerWeight(a, layer, f"{keys[-2]}_{keys[-1]}")
        return a if layer is None else jax.lax.dynamic_index_in_dim(
            a, layer, keepdims=False)
    return jax.tree_util.tree_map_with_path(leaf, block)


def _single_device() -> bool:
    """Whether the program being traced runs on one device: by the mesh it
    is traced under, or without one, by the devices the process has."""
    mesh = jax.sharding.get_abstract_mesh()
    return jax.device_count() == 1 if mesh.empty else mesh.size == 1


def matmul(x: jax.Array, w, preferred=None) -> jax.Array:
    """x @ w in x's dtype. A `LayerWeight` stored in another dtype than x
    (float32 weights, bf16 compute), read by at most `DECODE_ROWS` rows in
    a one-device program, goes through the decode_matmul kernel: its tiles
    are read from HBM as stored and cast in VMEM, so no cast copy of the
    weight is written. Anything else is cast (`cast_weight`) and multiplied
    by XLA. The kernel is a Mosaic call that GSPMD cannot partition, so a
    program over several devices keeps XLA's path."""
    if isinstance(w, LayerWeight):
        rows = int(np.prod(x.shape[:-1]))
        if (w.stack.dtype != x.dtype and rows <= DECODE_ROWS
                and _single_device()):
            y = decode_matmul_ops.decode_matmul(
                x.reshape(rows, x.shape[-1]), w.stack, w.layer,
                name=f"decode_matmul_{w.name}")
            return y.reshape(*x.shape[:-1], y.shape[-1])
        w = w.row()
    return jnp.einsum("...m,mn->...n", x, cast_weight(w, x.dtype),
                      preferred_element_type=preferred)


def dense(x: jax.Array, w, b: Optional[jax.Array] = None) -> jax.Array:
    y = matmul(x, w, prefer_dtype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap else x


ACTS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
}


# -- MLP --------------------------------------------------------------------


def mlp_specs(d_model: int, d_ff: int, glu: bool, pdt) -> dict[str, ParamSpec]:
    specs = {
        "wi": ParamSpec((d_model, d_ff), ("embed", "ffn"), pdt),
        "wo": ParamSpec((d_ff, d_model), ("ffn", "embed"), pdt),
    }
    if glu:
        specs["wg"] = ParamSpec((d_model, d_ff), ("embed", "ffn"), pdt)
    return specs


def mlp(params: dict, x: jax.Array, act: str, rules: dict) -> jax.Array:
    h = dense(x, params["wi"])
    h = constrain(h, rules, "batch", None, "ffn")
    a = ACTS[act](h)
    if "wg" in params:
        a = a * dense(x, params["wg"])
    y = dense(a, params["wo"])
    return constrain(y, rules, "batch", None, None)


# -- RoPE -------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # (B,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (B,S,1,half)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# -- Embedding --------------------------------------------------------------


def embed_specs(vocab: int, d_model: int, tie: bool, pdt) -> dict[str, ParamSpec]:
    specs = {"table": ParamSpec((vocab, d_model), ("vocab", "embed"), pdt, scale=1.0)}
    if not tie:
        specs["head"] = ParamSpec((d_model, vocab), ("embed", "vocab"), pdt)
    return specs


def embed(params: dict, tokens: jax.Array, scale: bool, dtype) -> jax.Array:
    x = cast_weight(params["table"], dtype)[tokens]
    if scale:
        x = x * jnp.asarray(np.sqrt(params["table"].shape[1]), dtype)
    return x


def unembed(params: dict, x: jax.Array, tie: bool) -> jax.Array:
    w = params["table"].T if tie else params["head"]
    return jnp.einsum("...m,mv->...v", x, cast_weight(w, x.dtype))
