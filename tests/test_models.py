"""Per-architecture smoke tests: reduced same-family config, one forward +
train step on CPU, shape and NaN checks; decode-vs-prefill consistency."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import Model

B, S = 2, 64


def _batch(cfg, key=0):
    k = jax.random.PRNGKey(key)
    batch = {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.n_prefix:
        batch["prefix_embed"] = jax.random.normal(
            k, (B, cfg.n_prefix, cfg.d_model), jnp.float32) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        return model.loss_fn(p, batch, rules={})

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert loss.shape == ()
    assert not jnp.isnan(loss), metrics
    gn = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gn)
    # one SGD step reduces loss on the same batch (sanity of gradients)
    lr = 0.02
    p2 = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    loss2, _ = model.loss_fn(p2, batch, rules={})
    assert float(loss2) < float(loss)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    cfg = get_config(arch).smoke()
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    if cfg.moe:  # capacity drops are train-time semantics; disable here
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0,
                              cfg.vocab_size)
    P = cfg.n_prefix
    pre = {}
    if P:
        pre["prefix_embed"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, P, cfg.d_model), jnp.float32) * 0.02
    lg_full, _ = model.prefill(params, toks, rules={}, **pre)
    lg_pre, cache = model.prefill(params, toks[:, :S], rules={},
                                  max_len=S + P + 8, **pre)
    lg_dec, _ = model.decode_step(params, toks[:, S:S + 1],
                                  jnp.full((B,), S + P, jnp.int32),
                                  cache, rules={})
    rel = float(jnp.max(jnp.abs(lg_full - lg_dec)) /
                (jnp.max(jnp.abs(lg_full)) + 1e-9))
    assert rel < 2e-4, f"{arch}: decode/prefill mismatch rel={rel}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_matches_specs(arch):
    """Analytic 6ND param count ~ materialized spec sizes (±2%)."""
    cfg = get_config(arch)
    model = Model(cfg)
    total = sum(int(np.prod(s.shape)) for s in
                jax.tree.leaves(model.param_specs(),
                                is_leaf=lambda x: hasattr(x, "logical_axes")))
    analytic = cfg.param_count()
    assert abs(total - analytic) / analytic < 0.02, (total, analytic)


def test_multi_token_decode_matches_prefill():
    """Decode 4 tokens sequentially == prefill of the longer sequence."""
    cfg = dataclasses.replace(get_config("gemma2-27b").smoke(),
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    n_new = 4
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, S + n_new), 0,
                              cfg.vocab_size)
    _, cache = model.prefill(params, toks[:, :S], rules={},
                             max_len=S + n_new)
    for t in range(n_new):
        lg_dec, cache = model.decode_step(
            params, toks[:, S + t:S + t + 1],
            jnp.full((1,), S + t, jnp.int32), cache, rules={})
    lg_full, _ = model.prefill(params, toks, rules={})
    rel = float(jnp.max(jnp.abs(lg_full - lg_dec)) /
                (jnp.max(jnp.abs(lg_full)) + 1e-9))
    assert rel < 2e-4, rel


def _layers(tree, pattern):
    """Per-layer list of a model's params or caches, in layer order: rows of
    the scanned stacks, then the unrolled remainder."""
    p = len(pattern)
    scan, rem = tree["scan"], tree["rem"]
    n_scan = jax.tree.leaves(scan)[0].shape[0] if scan else 0
    out = [jax.tree.map(lambda a, s=s: a[s], scan[f"{i}:{kind}"])
           for s in range(n_scan) for i, kind in enumerate(pattern)]
    return out + [rem[f"{j}:{pattern[j % p]}"] for j in range(len(rem))]


def _unrolled(tree, pattern):
    """The same tree keyed as a model with scan_layers=False keys it."""
    p = len(pattern)
    rem = {f"{l}:{pattern[l % p]}": layer
           for l, layer in enumerate(_layers(tree, pattern))}
    return {**tree, "scan": {}, "rem": rem}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_scanned_decode_matches_unrolled(arch):
    """Decode with the stacked caches carried through the layer scan and
    updated in place equals decode through the unrolled per-layer path:
    same logits, same caches, leaf by leaf."""
    base = get_config(arch).smoke()
    pat = base.layer_pattern
    # two scanned periods and one unrolled remainder layer
    cfg = dataclasses.replace(base, compute_dtype="float32",
                              n_layers=2 * len(pat) + 1)
    if cfg.moe:  # capacity drops are train-time semantics; disable here
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    scanned = Model(cfg)
    unrolled = Model(dataclasses.replace(cfg, scan_layers=False))
    params = scanned.init(jax.random.PRNGKey(7))
    assert params["scan"], "the scanned model must scan"
    n_new, P = 4, cfg.n_prefix
    toks = jax.random.randint(jax.random.PRNGKey(8), (B, S + n_new), 0,
                              cfg.vocab_size)
    pre = {}
    if P:
        pre["prefix_embed"] = jax.random.normal(
            jax.random.PRNGKey(9), (B, P, cfg.d_model), jnp.float32) * 0.02
    _, cache = scanned.prefill(params, toks[:, :S], rules={},
                               max_len=S + P + n_new, **pre)
    u_params, u_cache = _unrolled(params, pat), _unrolled(cache, pat)
    step = jax.jit(functools.partial(scanned.decode_step, rules={}))
    u_step = jax.jit(functools.partial(unrolled.decode_step, rules={}))
    for t in range(n_new):
        tok = toks[:, S + t:S + t + 1]
        pos = jnp.full((B,), S + P + t, jnp.int32)
        lg, cache = step(params, tok, pos, cache)
        u_lg, u_cache = u_step(u_params, tok, pos, u_cache)
        np.testing.assert_array_equal(lg, u_lg, err_msg=f"{arch} step {t}")
    layers, u_layers = _layers(cache, pat), _layers(u_cache, pat)
    assert len(layers) == len(u_layers) == cfg.n_layers
    for l, (c, u) in enumerate(zip(layers, u_layers)):
        assert c.keys() == u.keys()
        for name in c:
            np.testing.assert_array_equal(c[name], u[name],
                                          err_msg=f"{arch} layer {l} {name}")


def test_loss_mask_respected():
    cfg = get_config("musicgen-medium").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss_all, _ = model.loss_fn(params, batch, rules={})
    batch2 = dict(batch, loss_mask=batch["loss_mask"].at[:, S // 2:].set(0.0))
    loss_half, _ = model.loss_fn(params, batch2, rules={})
    assert not np.isclose(float(loss_all), float(loss_half))
    batch3 = dict(batch, targets=batch["targets"].at[:, S // 2:].set(0),
                  loss_mask=batch2["loss_mask"])
    loss_half2, _ = model.loss_fn(params, batch3, rules={})
    assert np.isclose(float(loss_half), float(loss_half2))  # masked targets ignored


def _kernel_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("arch", ["musicgen-medium", "recurrentgemma-9b"])
def test_decode_matmul_kernel_only_in_decode(arch):
    """Float32 weights with bf16 compute: decode's attention and MLP
    matmuls read their weights in the decode_matmul kernel; prefill and a
    training step, at as few rows, keep the cast-then-dot path, and so does
    decode where the weights are stored in the compute dtype."""
    cfg = get_config(arch).smoke()
    assert cfg.param_dtype == "float32" and cfg.compute_dtype == "bfloat16"
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 8  # 16 rows: as few as a decode step's
    batch = {k: v[:b, :s] for k, v in _batch(cfg).items()}
    _, cache = model.prefill(params, batch["tokens"], rules={}, max_len=s + 1)
    tok, pos = batch["tokens"][:, :1], jnp.full((b,), s, jnp.int32)

    def decode(p):
        return model.decode_step(p, tok, pos, cache, rules={})

    assert _kernel_calls(decode, params) > 0
    assert _kernel_calls(lambda p: model.prefill(
        p, batch["tokens"], rules={}, max_len=s + 1), params) == 0
    assert _kernel_calls(jax.grad(lambda p: model.loss_fn(
        p, batch, rules={})[0]), params) == 0
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                        if a.dtype == jnp.float32 else a, params)
    assert _kernel_calls(decode, bf16) == 0


def test_float32_weight_decode_matches_precast_bf16():
    """A decode step over float32 weights (the kernel casts each tile in
    VMEM) gives the logits of the same step over the same weights cast to
    bf16 beforehand (XLA's dot, no kernel), to the rounding of a reordered
    sum."""
    cfg = dataclasses.replace(get_config("musicgen-medium").smoke(),
                              n_layers=3)  # two scanned layers, one unrolled
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    matrices = ("wq", "wk", "wv", "wo", "wi", "wg")
    precast = jax.tree_util.tree_map_with_path(
        lambda path, a: a.astype(jnp.bfloat16)
        if getattr(path[-1], "key", None) in matrices else a, params)
    toks = jax.random.randint(jax.random.PRNGKey(12), (B, S + 1), 0,
                              cfg.vocab_size)
    _, cache = model.prefill(params, toks[:, :S], rules={}, max_len=S + 1)

    def step(p):
        return model.decode_step(p, toks[:, S:S + 1],
                                 jnp.full((B,), S, jnp.int32), cache,
                                 rules={})[0]

    assert _kernel_calls(step, params) == 12  # 6 in the scan body, 6 after
    assert _kernel_calls(step, precast) == 0
    lg = np.asarray(jax.jit(step)(params), np.float32)
    lg_ref = np.asarray(jax.jit(step)(precast), np.float32)
    np.testing.assert_allclose(lg, lg_ref, rtol=2 ** -7,
                               atol=1e-2 * np.abs(lg_ref).max())
    # a reordered sum seldom moves a logit; products of the uncast weights
    # move most of them
    assert (lg != lg_ref).mean() <= 0.01
