import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# Multi-device checks, run as a subprocess from test_distributed.py so the
# main pytest process keeps the default single-device view.
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM, device_put_batch
from repro.launch.mesh import make_debug_mesh
from repro.models import Model
from repro.train import TrainConfig, TrainSetup


def batch_for(cfg, B, S, rules, mesh, seed=0):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=S, global_batch=B,
                    seed=seed, n_prefix=cfg.n_prefix, d_model=cfg.d_model)
    return device_put_batch(next(SyntheticLM(dc).batches()), mesh, rules)


def check_sharded_equals_single():
    """Train step on a 2x2 mesh == single-device step (same math)."""
    import dataclasses
    cfg = dataclasses.replace(get_config("gemma2-27b").smoke(),
                              compute_dtype="float32",
                              param_dtype="float32")
    model = Model(cfg)
    B, S = 4, 64

    mesh1 = make_debug_mesh(1, 1)
    mesh2 = make_debug_mesh(2, 2)
    tc = TrainConfig(egress="none")
    s1 = TrainSetup(model, mesh1, tc)
    s2 = TrainSetup(model, mesh2, tc)
    st1 = s1.init_state(jax.random.PRNGKey(7))
    # same initial params on the other mesh
    st2 = jax.device_put(jax.tree.map(np.asarray, st1),
                         s2.state_shardings())
    b = next(SyntheticLM(DataConfig(cfg.vocab_size, 64, 4, seed=1)
                         if False else
             DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                        global_batch=B, seed=1)).batches())
    b1 = device_put_batch(b, mesh1, s1.rules)
    b2 = device_put_batch(b, mesh2, s2.rules)
    with jax.set_mesh(mesh1):
        n1, m1, _ = jax.jit(s1.step_fn())(st1, b1)
    with jax.set_mesh(mesh2):
        n2, m2, _ = jax.jit(s2.step_fn())(st2, b2)
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert abs(l1 - l2) / abs(l1) < 1e-5, (l1, l2)
    g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
    assert abs(g1 - g2) / abs(g1) < 1e-4, (g1, g2)
    # updated params equal
    p1 = jax.tree.leaves(jax.tree.map(np.asarray, n1["params"]))
    p2 = jax.tree.leaves(jax.tree.map(np.asarray, n2["params"]))
    worst = max(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
                for a, b in zip(p1, p2))
    assert worst < 1e-4, worst
    print("check_sharded_equals_single OK", l1, l2)


def check_compressed_pod_reduce():
    """int8 EF cross-pod reduce ~= exact mean; error feedback shrinks bias."""
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen2-72b").smoke(),
                              compute_dtype="float32",
                              param_dtype="float32")
    model = Model(cfg)
    mesh = make_debug_mesh(2, 2, pod=2)
    tc = TrainConfig(egress="none", compress_pods=True)
    setup = TrainSetup(model, mesh, tc)
    assert setup.compress
    st = setup.init_state(jax.random.PRNGKey(3))
    B, S = 4, 32
    b = device_put_batch(
        next(SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                                    global_batch=B, seed=2)).batches()),
        mesh, setup.rules)
    with jax.set_mesh(mesh):
        step = jax.jit(setup.step_fn())
        losses = []
        for i in range(4):
            st, m, _ = step(st, b)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses  # learns on the fixed batch
    print("check_compressed_pod_reduce OK", [round(l, 4) for l in losses])


def check_compressed_reduce_nondivisible():
    """Regression: compressed_pod_allreduce at ceil(n/QBLOCK) % n_pods != 0.

    error_state row-pads to a multiple of n_pods; _flatten historically did
    not, so `g + e` inside the shard_map body shape-mismatched whenever the
    block-row count was not divisible by the pod count.
    """
    from repro.optim import grad_compress as gc
    mesh = make_debug_mesh(2, 2, pod=2)
    n_pods = mesh.shape["pod"]
    rng = np.random.default_rng(7)
    # 2*QBLOCK + 12 elements -> 3 block rows; 3 % 2 != 0 hits the bug.
    tree = {"w": jnp.asarray(rng.standard_normal(2 * gc.QBLOCK + 5),
                             jnp.float32),
            "b": jnp.asarray(rng.standard_normal(7), jnp.float32)}
    st = gc.error_state(tree, n_pods)
    assert st.shape[0] % n_pods == 0 and st.shape[0] == 4
    flat, pad = gc._flatten(tree, n_pods)
    assert flat.shape == st.shape, (flat.shape, st.shape)
    err = jnp.zeros(st.shape, st.dtype)
    red, new_err = gc.compressed_pod_allreduce(tree, err, mesh)
    assert new_err.shape == st.shape
    # replicated input -> mean over pods == double-quantized round-trip
    for k in tree:
        x, y = np.asarray(tree[k]), np.asarray(red[k])
        atol = 2.1 * np.abs(x).max() / 127.0   # RS + AG quant stages
        assert np.allclose(x, y, rtol=0, atol=atol), k
    print("check_compressed_reduce_nondivisible OK")


def check_reshard_restore():
    """Checkpoint on a (1,4) mesh, restore on (4,1) and (2,2) — elastic."""
    import dataclasses
    import tempfile
    from repro.checkpoint import CheckpointManager
    cfg = dataclasses.replace(get_config("falcon-mamba-7b").smoke(),
                              compute_dtype="float32")
    model = Model(cfg)
    tc = TrainConfig(egress="none")
    mA = make_debug_mesh(1, 4)
    sA = TrainSetup(model, mA, tc)
    stA = sA.init_state(jax.random.PRNGKey(9))
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d, async_writes=False)
        ck.save(stA, 1)
        for shape in ((4, 1), (2, 2)):
            mB = make_debug_mesh(*shape)
            sB = TrainSetup(model, mB, tc)
            stB = ck.restore(sB.abstract_state(),
                             shardings=sB.state_shardings())
            a = jax.tree.leaves(jax.tree.map(np.asarray, stA["params"]))
            b = jax.tree.leaves(jax.tree.map(np.asarray, stB["params"]))
            assert all(np.array_equal(x, y) for x, y in zip(a, b))
    print("check_reshard_restore OK")


def check_seq_sharded_decode():
    """SP decode: seq-sharded KV cache == replicated-cache decode."""
    import dataclasses
    from repro.train.serve_step import ServeSetup
    smoke = get_config("gemma3-4b").smoke()
    # two scanned periods (caches carried through the layer scan) and an
    # unrolled remainder layer
    cfg = dataclasses.replace(smoke, compute_dtype="float32",
                              n_layers=2 * len(smoke.layer_pattern) + 1)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    B, S = 1, 64
    toks = jax.random.randint(jax.random.PRNGKey(12), (B, S + 1), 0,
                              cfg.vocab_size)
    # reference on default device
    _, cache = model.prefill(params, toks[:, :S], rules={}, max_len=S + 8)
    ref_lg, _ = model.decode_step(params, toks[:, S:S + 1],
                                  jnp.full((B,), S, jnp.int32), cache,
                                  rules={})
    mesh = make_debug_mesh(4, 2)
    setup = ServeSetup(model, mesh, seq_shard_kv=True, global_batch=B)
    ps = jax.device_put(jax.tree.map(np.asarray, params),
                        setup.param_shardings())
    cs = jax.device_put(jax.tree.map(np.asarray, cache),
                        setup.cache_shardings(B, S + 8))
    with jax.set_mesh(mesh):
        lg, new_cs = setup.jitted_decode(B, S + 8)(
            ps, cs, {"tokens": toks[:, S:S + 1],
                     "pos": jnp.full((B,), S, jnp.int32)})
    want = setup.cache_shardings(B, S + 8)
    for got, w in zip(jax.tree.leaves(new_cs), jax.tree.leaves(want)):
        assert got.sharding.is_equivalent_to(w, got.ndim), (got.sharding, w)
    rel = float(jnp.max(jnp.abs(lg - ref_lg)) /
                (jnp.max(jnp.abs(ref_lg)) + 1e-9))
    assert rel < 1e-4, rel
    print("check_seq_sharded_decode OK", rel)


CHECKS = {f.__name__: f for f in (
    check_sharded_equals_single, check_compressed_pod_reduce,
    check_compressed_reduce_nondivisible,
    check_reshard_restore, check_seq_sharded_decode)}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
