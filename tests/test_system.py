"""End-to-end behaviour tests for the in-transit staging system (the paper's
Listing-1 flow), fault tolerance, and the transfer-engine baselines."""
import os
import time

import numpy as np
import pytest

from repro.core import (
    Dataset, InTransitConfig, InTransitSink, SavimeClient, SavimeServer,
    StagingClient, StagingServer,
)
from repro.transport import TransportConfig, run_engine


@pytest.fixture()
def savime():
    srv = SavimeServer().start()
    yield srv
    srv.stop()


@pytest.fixture()
def staging(savime):
    srv = StagingServer(savime.addr, mem_capacity=64 << 20,
                        send_threads=2).start()
    yield srv
    srv.stop()


def test_paper_listing1_flow(savime, staging):
    """create_tar -> dataset.write -> sync -> load_subtar -> query."""
    cli = StagingClient(staging.addr, io_threads=2, block_size=256 << 10)
    try:
        cli.run_savime('create_tar(vel, "x:0:15, y:0:31, z:0:31", "v:float64")')
        v = np.random.default_rng(0).standard_normal((16, 32, 32))
        Dataset("D", "float64", cli).write(v)
        cli.sync()          # paper: block until writes reach staging
        cli.drain()         # staging -> SAVIME finished
        cli.run_savime('load_subtar(vel, D, "0,0,0", "16,32,32", v)')
        assert np.isclose(cli.run_savime("aggregate(vel, v, mean)"), v.mean())
        direct = SavimeClient(savime.addr)
        got = direct.run('select(vel, v, "0,0,0", "3,3,3")')
        assert np.array_equal(got, v[:4, :4, :4])
    finally:
        cli.close()


def test_multi_client_concurrent_ingest(savime, staging):
    """Several 'compute nodes' writing concurrently (paper's 5 clients)."""
    clients = [StagingClient(staging.addr, io_threads=2,
                             block_size=128 << 10) for _ in range(3)]
    rng = np.random.default_rng(1)
    try:
        for i, cli in enumerate(clients):
            for j in range(3):
                Dataset(f"n{i}_f{j}", "float64", cli).write(
                    rng.standard_normal(4096))
        for cli in clients:
            cli.sync()
        clients[0].drain()
        assert clients[0].stats()["datasets"] == 9
        assert SavimeClient(savime.addr).stats()["datasets"] == 9
    finally:
        for cli in clients:
            cli.close()


def test_disk_fallback(savime):
    """Paper §3.1: if the in-memory FS is full, disk is the fallback."""
    staging_srv = StagingServer(savime.addr, mem_capacity=1 << 10,  # 1 KiB
                                send_threads=1).start()
    cli = StagingClient(staging_srv.addr, io_threads=1, block_size=1 << 20)
    try:
        Dataset("big", "float64", cli).write(np.ones(65536))
        cli.sync()
        assert cli.stats()["disk_fallbacks"] >= 1
        cli.drain()
    finally:
        cli.close()
        staging_srv.stop()


def test_block_registration_on_demand(savime, staging):
    cli = StagingClient(staging.addr, io_threads=1, block_size=16 << 10)
    try:
        Dataset("d", "float64", cli).write(np.ones(16384))  # 128 KiB
        cli.sync()
        assert cli.stats()["registrations"] == 8  # 128K / 16K blocks
    finally:
        cli.close()


def test_intransit_sink_roundtrip(savime, staging):
    sink = InTransitSink(staging.addr, InTransitConfig(io_threads=2))
    field = np.random.default_rng(2).standard_normal((4, 8, 8)).astype(np.float32)
    for step in range(3):
        sink.stage_array("field", field * (step + 1), step=step)
    sink.flush()
    got = SavimeClient(savime.addr).run('select(run_field, v, "1,0,0,0", "1,3,7,7")')
    assert np.allclose(got[0], field * 2)
    sink.close()


def test_intransit_sink_quantized(savime, staging):
    from repro.core.intransit import dequantize_int8_np
    sink = InTransitSink(staging.addr,
                         InTransitConfig(quantize="int8", tar_prefix="q"))
    x = np.random.default_rng(3).standard_normal((32, 32)).astype(np.float32)
    sink.stage_array("act", x, step=0)
    sink.flush()
    direct = SavimeClient(savime.addr)
    q = direct.run("select(q_act, v)")
    s = direct.run("select(q_act__scale, s)")
    deq = dequantize_int8_np(q[0], s[0][: max(q[0].size // 4096, 1)],
                             x.shape, 4096)
    assert np.abs(deq - x).max() <= np.abs(x).max() / 127 + 1e-6
    sink.close()


# ---------------------------------------------------------------------------
# Baseline engines (paper Fig 6 at test scale: all deliver, bytes conserved)
# ---------------------------------------------------------------------------


def test_engines_all_deliver(savime):
    """Engines are named only via the transport registry."""
    rng = np.random.default_rng(4)
    bufs = [rng.standard_normal(1 << 14) for _ in range(4)]
    results = []
    for tag, engine in (("a", "rdma_staged"), ("b", "scp_mem"),
                        ("c", "ssh_direct")):
        cfg = TransportConfig(savime_addr=savime.addr, block_size=64 << 10,
                              io_threads=2)
        results.append(run_engine(engine, bufs,
                                  [f"{tag}{i}" for i in range(4)], cfg))
    assert SavimeClient(savime.addr).stats()["datasets"] == 12
    assert min(r.nbytes for r in results) == sum(b.nbytes for b in bufs)


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------


def test_straggler_speculation():
    from repro.core.queues import FCFSPool
    slow_once = {"done": False}

    def work(i):
        if i == 0 and not slow_once["done"]:
            slow_once["done"] = True
            time.sleep(1.0)       # straggler
        return i

    pool = FCFSPool(2, "t", straggler_timeout=0.2)
    hs = [pool.submit(work, i, name=f"w{i}") for i in range(4)]
    for h in hs:
        h.wait(5)
    assert any(h.speculative for h in hs)
    pool.stop()


def test_pool_retry_then_fail():
    from repro.core.queues import FCFSPool
    pool = FCFSPool(1, "t", max_retries=2)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert pool.submit(flaky, name="flaky").wait(5) == "ok"

    def always_fails():
        raise OSError("hard")

    h = pool.submit(always_fails, name="hard")
    with pytest.raises(OSError):
        h.wait(5)
    pool.stop()


def test_supervisor_restores_from_checkpoint(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.runtime import Supervisor, SupervisorConfig

    def step_fn(state, batch):
        new = {"w": state["w"] + batch["x"], "step": state["step"] + 1}
        return new, {"loss": jnp.sum(new["w"])}, {}

    ckpt = CheckpointManager(str(tmp_path), async_writes=False)
    sup = Supervisor(step_fn, ckpt, SupervisorConfig(ckpt_every=2,
                                                     max_restarts=2))
    state = {"w": jnp.zeros(4), "step": jnp.zeros((), jnp.int32)}
    batches = iter(lambda: {"x": jnp.ones(4)}, None)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    out = sup.run(state, batches, 7, abstract_state=abstract, fail_at={5})
    assert int(out["step"]) == 7
    assert sup.restarts == 1
    assert np.allclose(np.asarray(out["w"]), 7.0)


def test_supervisor_saves_each_checkpoint_step_once(tmp_path):
    """A run whose last step is a checkpoint step saves it once, not a
    second time as the final checkpoint."""
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.runtime import Supervisor, SupervisorConfig

    class Recording(CheckpointManager):
        def save(self, state, step):
            self.steps.append(step)
            return super().save(state, step)

    def step_fn(state, batch):
        return {"step": state["step"] + 1}, {}, {}

    for n_steps, want in ((4, [2, 4]), (5, [2, 4, 5])):
        ckpt = Recording(str(tmp_path / str(n_steps)), async_writes=False)
        ckpt.steps = []
        Supervisor(step_fn, ckpt, SupervisorConfig(ckpt_every=2)).run(
            {"step": jnp.zeros((), jnp.int32)}, iter(lambda: {}, None),
            n_steps)
        assert ckpt.steps == want


def test_supervisor_restart_budget_exceeded(tmp_path):
    """Burning through max_restarts raises the typed error, and the
    message carries the last committed checkpoint step (enough to resume
    the run by hand)."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.runtime import (InjectedFailure, RestartBudgetExceeded,
                               Supervisor, SupervisorConfig)

    def step_fn(state, batch):
        if int(state["step"]) >= 4:
            raise InjectedFailure("poisoned step")
        new = {"w": state["w"] + batch["x"], "step": state["step"] + 1}
        return new, {"loss": jnp.sum(new["w"])}, {}

    ckpt = CheckpointManager(str(tmp_path), async_writes=False)
    sup = Supervisor(step_fn, ckpt, SupervisorConfig(ckpt_every=2,
                                                     max_restarts=2))
    state = {"w": jnp.zeros(4), "step": jnp.zeros((), jnp.int32)}
    batches = iter(lambda: {"x": jnp.ones(4)}, None)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    with pytest.raises(RestartBudgetExceeded) as ei:
        sup.run(state, batches, 8, abstract_state=abstract)
    assert sup.restarts == 3
    assert ei.value.last_checkpoint_step == 4
    assert "step 4" in str(ei.value)
    assert "max_restarts=2" in str(ei.value)


def test_supervisor_fail_at_composes_with_staging_checkpoint(
        tmp_path, savime, staging):
    """fail_at injection + a staging-path (sink-backed) checkpoint: the
    run restores from the analyzable checkpoint and finishes, and the
    checkpoint shards are queryable at SAVIME."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.runtime import Supervisor, SupervisorConfig

    sink = InTransitSink(staging.addr, InTransitConfig(tar_prefix="ckpt"))
    try:
        def step_fn(state, batch):
            new = {"w": state["w"] + batch["x"],
                   "step": state["step"] + 1}
            return new, {"loss": jnp.sum(new["w"])}, {}

        ckpt = CheckpointManager(str(tmp_path), sink=sink,
                                 async_writes=False)
        sup = Supervisor(step_fn, ckpt, SupervisorConfig(ckpt_every=2,
                                                         max_restarts=2))
        state = {"w": jnp.zeros(4), "step": jnp.zeros((), jnp.int32)}
        batches = iter(lambda: {"x": jnp.ones(4)}, None)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        out = sup.run(state, batches, 5, abstract_state=abstract,
                      fail_at={3})
        assert int(out["step"]) == 5
        assert sup.restarts == 1
        assert np.allclose(np.asarray(out["w"]), 5.0)
        sink.flush()
        direct = SavimeClient(savime.addr)
        tars = str(direct.run("list_tars()"))
        assert "ckpt_" in tars, "staged checkpoint shards should be queryable"
    finally:
        sink.close()


def test_checkpoint_reshard_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), async_writes=True)
    state = {"a": jnp.arange(16.0).reshape(4, 4),
             "nested": {"b": jnp.ones((8,), jnp.int32)},
             "step": jnp.int32(3)}
    ckpt.save(state, 3)
    ckpt.wait()
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    back = ckpt.restore(abstract)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(state), jax.tree.leaves(back)))


def test_checkpoint_wait_raises_on_failed_write(tmp_path, monkeypatch):
    """A failed async checkpoint write surfaces at wait(), not as a
    silent pool counter."""
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager, checkpointing

    def broken_save(*a, **kw):
        raise OSError("disk full")

    ckpt = CheckpointManager(str(tmp_path), async_writes=True)
    monkeypatch.setattr(checkpointing.np, "save", broken_save)
    ckpt.save({"a": jnp.ones(4)}, 1)
    with pytest.raises(RuntimeError, match="ckpt-1.*disk full"):
        ckpt.wait()
    monkeypatch.undo()
    ckpt.save({"a": jnp.ones(4)}, 2)
    ckpt.wait()                      # the failure was reported once
    assert ckpt.latest_step() == 2


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR decides where the cache goes; without it
    the cache is the fixed .jax_cache/ at the repository root."""
    import jax
    from repro.runtime import compile_cache

    was = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            want = os.path.join(os.path.dirname(__file__), "..", ".jax_cache")
            assert os.path.samefile(os.path.dirname(got),
                                    os.path.dirname(want))
            assert got.endswith(".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_elastic_mesh_plan():
    from repro.runtime import plan_mesh
    assert plan_mesh(512) == ((2, 16, 16), ("pod", "data", "model"))
    assert plan_mesh(256) == ((16, 16), ("data", "model"))
    # degraded: 480 chips -> single-pod mesh of the remainder
    assert plan_mesh(480) == ((30, 16), ("data", "model"))
    with pytest.raises(ValueError):
        plan_mesh(100, model_parallel=16)
