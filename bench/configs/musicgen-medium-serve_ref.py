"""Plain reference of the musicgen-medium-serve configuration: its weights,
made from the seed, and its forward pass in float32 ``jax.numpy``.

The decoder follows the configuration's ``model`` group and its
``assumed`` notes: token embedding, then per layer a pre-norm causal
self-attention with rotary positions (half-split rotation) and a pre-norm
MLP with tanh-approximated GELU, each added to the residual stream; a
final norm and an untied output head. Norms are RMS norms with a learned
scale. Every product runs at ``Precision.HIGHEST`` so the TPU does not
round float32 operands to bfloat16. No cache, no batching tricks, no
kernels: each call recomputes the whole sequence.

``forward(..., quant="fp8")`` is the control: the pass a program one
precision below the configuration's bfloat16 compute would make. Every
matrix product takes float8 (e4m3) operands, with one scale per weight
matrix and one per activation row, and every activation between them,
the residual stream included, is rounded to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(m: dict) -> dict:
    L, M, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    return {"table": (V, M), "head": (M, V), "final_ln": (M,),
            "ln1": (L, M), "ln2": (L, M),
            "wq": (L, M, q), "wk": (L, M, kv), "wv": (L, M, kv),
            "wo": (L, q, M), "wi": (L, M, F), "wf": (L, F, M)}


def make_weights(key, m: dict) -> dict:
    """All weights in one jitted call on the default device, in the
    configuration's parameter dtype. Matrices are normal with std
    1/sqrt(fan-in), the embedding table has std 1, norm scales are
    1 + 0.05 * normal."""
    shapes = weight_shapes(m)
    dt = jnp.dtype(m["param_dtype"])

    def build(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, shape) in zip(keys, sorted(shapes.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            if name in ("final_ln", "ln1", "ln2"):
                w = 1.0 + 0.05 * z
            elif name == "table":
                w = z
            else:
                w = z / np.sqrt(shape[-2])
            out[name] = w.astype(dt)
        return out

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x, quant):
    if quant == "fp8":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(x, w, quant):
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return _bf16(jnp.einsum("...m,mn->...n", x, w, precision=HIGHEST), quant)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq               # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w: dict, tokens, m: dict, quant=None):
    """Logits (B, S, V) in float32 at every position of `tokens` (B, S)."""
    B, S = tokens.shape
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = _bf16(f32["table"][tokens], quant)
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                        # (q, k)

    def layer(x, lw):
        h = _bf16(_rms(x, lw["ln1"], eps), quant)
        q = _mm(h, lw["wq"], quant).reshape(B, S, H, D)
        k = _mm(h, lw["wk"], quant).reshape(B, S, Hkv, D)
        v = _mm(h, lw["wv"], quant).reshape(B, S, Hkv, D)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        if quant == "fp8":
            q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _fp8(p, -1)
        o = _bf16(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST),
                  quant)
        x = _bf16(x + _mm(o.reshape(B, S, H * D), lw["wo"], quant), quant)
        h2 = _bf16(_rms(x, lw["ln2"], eps), quant)
        a = _bf16(jax.nn.gelu(_mm(h2, lw["wi"], quant), approximate=True),
                  quant)
        return _bf16(x + _mm(a, lw["wf"], quant), quant), None

    stacked = {k: f32[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                   "wi", "wf")}
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _bf16(_rms(x, f32["final_ln"], eps), quant)
    return _mm(x, f32["head"], quant)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def served_logits(w: dict, prompts, served, m: dict, quant=None):
    """Logits (B, N, V) at the N positions that produced ``served``.

    prompts (B, S) and served (B, N) int: served[:, j] was produced after
    the prompt and served[:, :j], so one pass over the prompt and all but
    the last served token gives every position's logits."""
    prompts = np.asarray(prompts)
    served = np.asarray(served)
    S, N = prompts.shape[1], served.shape[1]
    seq = np.concatenate([prompts, served[:, :N - 1]], axis=1)
    fwd = jax.jit(forward, static_argnames=("m", "quant"))
    return np.asarray(fwd(w, seq, _Frozen(m), quant=quant)[:, S - 1:])


def judge(ref: np.ndarray, tokens, logits: np.ndarray) -> dict:
    """The numbers a run is judged by, against the reference's logits.

    ``logit_gap``: the widest gap by which a served token's reference logit
    lies below the reference's best at its position (0 where every token is
    the reference's greedy choice). ``logit_err``: the largest difference
    between a served logit and the reference's, over the largest reference
    logit (the logits' relative error)."""
    tokens = np.asarray(tokens)
    got = np.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    gap = ref.max(axis=-1) - got
    err = np.abs(np.asarray(logits, np.float32) - ref).max() \
        / np.abs(ref).max()
    return {"logit_gap": float(gap.max()), "logit_err": float(err),
            "tokens_off_best": int((gap > 0).sum()), "tokens": int(gap.size)}


def control(w: dict, prompts, served, m: dict, ref: np.ndarray) -> dict:
    """The control's numbers: the fp8 pass in the program's place, judged
    on the tokens it puts first and the logits it gives."""
    ctl = served_logits(w, prompts, served, m, quant="fp8")
    return judge(ref, ctl.argmax(axis=-1), ctl)


class _Frozen(dict):
    """A hashable view of the sizes, so they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
