"""Transport-API tests: registry round-trip, TransferSession semantics,
backpressure bound, TransferStats parity across all four engines, the
legacy shims' deprecation, and connection hygiene."""
import socket
import threading

import numpy as np
import pytest

from repro.core import SavimeClient, SavimeServer, StagingClient, StagingServer
from repro.core import wire
from repro import transport
from repro.transport import (TransferSession, TransferStats, TransportConfig,
                             UnknownTransportError, run_engine)

ALL_ENGINES = ("rdma_staged", "scp_mem", "scp_disk", "ssh_direct")


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


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_lists_all_engines():
    names = transport.available()
    for engine in ALL_ENGINES:
        assert engine in names


def test_registry_create_roundtrip(savime):
    cfg = TransportConfig(savime_addr=savime.addr)
    for engine in ALL_ENGINES:
        t = transport.create(engine, cfg)
        assert t.name == engine
        assert transport.get(engine) is type(t)


def test_registry_unknown_name_error():
    with pytest.raises(UnknownTransportError) as ei:
        transport.create("carrier_pigeon", TransportConfig())
    msg = str(ei.value)
    assert "carrier_pigeon" in msg and "rdma_staged" in msg


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError):
        @transport.register_transport("rdma_staged")
        class Impostor(transport.Transport):  # pragma: no cover - decorator raises
            def open(self): ...
            def write(self, name, dtype, buf): ...
            def sync(self, timeout=None): ...
            def drain(self, timeout=None): ...
            def close(self): ...


# ---------------------------------------------------------------------------
# TransferSession semantics
# ---------------------------------------------------------------------------


def test_session_context_manager_semantics(staging):
    cfg = TransportConfig(staging_addr=staging.addr, io_threads=1,
                          block_size=64 << 10)
    sess = TransferSession("rdma_staged", cfg)
    with pytest.raises(RuntimeError):          # not opened yet
        sess.write("x", np.ones(8))
    with sess:
        fut = sess.write("x", np.ones(1024))
        assert fut.name == "x" and fut.nbytes == 1024 * 8
    # clean exit synced + drained + closed
    assert fut.done()
    assert sess.stats.n_datasets == 1
    assert sess.stats.nbytes == 1024 * 8
    assert sess.stats.end_to_end_s >= sess.stats.to_staging_s > 0
    with pytest.raises(RuntimeError):          # closed: no further writes
        sess.write("y", np.ones(8))


def test_session_metrics_hooks(staging, tmp_path):
    """The session's instrumentation is its spans: one write, sync and
    drain under an active profiler session leave their records, and the
    same calls with the profiler off leave none."""
    import jax
    from repro import obs
    cfg = TransportConfig(staging_addr=staging.addr)
    with TransferSession("rdma_staged", cfg) as sess:
        jax.profiler.start_trace(str(tmp_path))
        try:
            sess.write("m", np.ones(64))
            sess.sync()
            sess.drain()
        finally:
            jax.profiler.stop_trace()
        traced = obs.spans()
        sess.write("n", np.ones(64))
        sess.sync()
        sess.drain()
        assert obs.spans() == traced
    names = [s.name for s in traced]
    for expected in ("session.write", "session.sync", "session.drain"):
        assert names.count(expected) == 1
    write, = (s for s in traced if s.name == "session.write")
    assert write.attrs["ds"] == "m" and write.attrs["bytes"] == 64 * 8
    assert write.attrs["wait_s"] >= 0


def test_backpressure_bounds_inflight_bytes(staging):
    nbuf, size = 8, 64 << 10
    bound = 2 * size * 8                     # two float64 buffers in flight
    cfg = TransportConfig(staging_addr=staging.addr, io_threads=1,
                          block_size=16 << 10, max_inflight_bytes=bound)
    with TransferSession("rdma_staged", cfg) as sess:
        for i in range(nbuf):
            sess.write(f"bp{i}", np.ones(size))
        sess.sync()
    assert sess.stats.peak_inflight_bytes <= bound
    assert sess.stats.n_datasets == nbuf


def test_backpressure_admits_oversized_buffer_alone(staging):
    cfg = TransportConfig(staging_addr=staging.addr,
                          max_inflight_bytes=1024)   # << buffer size
    with TransferSession("rdma_staged", cfg) as sess:
        fut = sess.write("big", np.ones(64 << 10))   # must not deadlock
        sess.sync()
        assert fut.done()


def test_exit_does_not_overwrite_phase_timings(staging):
    """The redundant sync/drain on clean __exit__ must not inflate the
    recorded phase timings (fig6's slowdown ratios depend on them)."""
    cfg = TransportConfig(staging_addr=staging.addr, block_size=64 << 10)
    with TransferSession("rdma_staged", cfg) as sess:
        sess.write("t0", np.ones(1 << 14))
        sess.sync()
        to_staging = sess.stats.to_staging_s
        sess.drain()
        end_to_end = sess.stats.end_to_end_s
    assert sess.stats.to_staging_s == to_staging
    assert sess.stats.end_to_end_s == end_to_end


def test_close_without_sync_completes_inflight_write(staging):
    """stop() joins in-flight transfers before closing their sockets: a
    write that was going to succeed still succeeds when the client closes
    immediately (the old facade allowed exactly this)."""
    cli = StagingClient(staging.addr, io_threads=1, block_size=1 << 20)
    fut = cli.session.write("eager_close", np.ones(1 << 20))  # 8 MiB
    cli.close()                 # no sync(): join must let the write finish
    assert fut.done()
    assert fut.wait(1) == 8 << 20


def test_stats_parity_across_transports(savime):
    """All four engines report the same TransferStats contract."""
    rng = np.random.default_rng(7)
    bufs = [rng.standard_normal(1 << 12) for _ in range(3)]
    total = sum(b.nbytes for b in bufs)
    for engine in ALL_ENGINES:
        cfg = TransportConfig(savime_addr=savime.addr, block_size=32 << 10,
                              io_threads=2)
        stats = run_engine(engine, bufs,
                           [f"{engine}_p{i}" for i in range(3)], cfg)
        assert isinstance(stats, TransferStats)
        assert stats.engine == engine
        assert stats.nbytes == total
        assert stats.n_datasets == 3
        assert stats.to_staging_s > 0
        assert stats.end_to_end_s >= stats.to_staging_s
        assert stats.staging_gbps > 0
    assert SavimeClient(savime.addr).stats()["datasets"] == 4 * 3


# ---------------------------------------------------------------------------
# legacy shims
# ---------------------------------------------------------------------------


def test_legacy_engine_shims_are_gone():
    # the deprecation shims (kept "for one release") are retired: the
    # module must fail to import cleanly, and the real API must not have
    # grown accidental aliases of the old names
    with pytest.raises(ImportError):
        import repro.core.transfer  # noqa: F401
    import repro.core as core
    for old in ("run_rdma_staged", "run_scp", "run_ssh_direct",
                "ENGINES", "TransferResult"):
        assert not hasattr(core, old)


# ---------------------------------------------------------------------------
# emulation-path hardening (frame validation + connection hygiene)
# ---------------------------------------------------------------------------


def test_tunnel_hop_rejects_unknown_op(savime):
    from repro.transport.copyemu import _CopyServerFwdToSavime
    hop = _CopyServerFwdToSavime(savime.addr)
    try:
        sock = wire.connect(hop.addr)
        try:
            # frame without op=fwd must be rejected, not silently sunk
            h, _ = wire.request(sock, {"name": "evil", "dtype": "uint8"},
                                b"\x00" * 64)
            assert h["ok"] is False and "fwd" in h["error"]
            # well-formed fwd frame still lands
            h, _ = wire.request(sock, {"op": "fwd", "name": "good",
                                       "dtype": "uint8"}, b"\x01" * 64)
            assert h["ok"] is True
        finally:
            sock.close()
        stats = SavimeClient(savime.addr).stats()
        assert stats["datasets"] == 1
    finally:
        hop.stop()


def test_communicator_sockets_closed_on_stop(staging):
    cli = StagingClient(staging.addr, io_threads=2, block_size=32 << 10)
    for i in range(3):
        cli.session.write(f"s{i}", np.ones(2048))
    cli.sync()
    comm = cli.comm
    socks = list(comm._socks._all)
    assert socks, "I/O threads should have opened per-thread sockets"
    cli.close()
    assert all(s.fileno() == -1 for s in socks)


@pytest.mark.parametrize("engine", ["scp_mem", "ssh_direct"])
def test_copy_engine_sockets_closed_on_close(savime, engine):
    cfg = TransportConfig(savime_addr=savime.addr, io_threads=2)
    sess = TransferSession(engine, cfg).open()
    for i in range(3):
        sess.write(f"h{i}", np.ones(2048))
    sess.sync()
    sess.drain()
    socks = list(sess.transport._socks._all)
    assert socks, "emulation clients should have opened per-thread sockets"
    sess.close()
    assert all(s.fileno() == -1 for s in socks)


def test_pool_stop_runs_cleanup_callbacks():
    from repro.core.queues import FCFSPool
    closed = threading.Event()
    pool = FCFSPool(1, "cleanup-test")
    pool.add_stop_callback(closed.set)
    pool.submit(lambda: None, name="noop").wait(5)
    pool.stop()
    assert closed.is_set()


# ---------------------------------------------------------------------------
# sink over a non-default transport (the API opens new workloads)
# ---------------------------------------------------------------------------


def test_intransit_sink_over_copy_transport(savime):
    from repro.core import InTransitConfig, InTransitSink
    sink = InTransitSink(savime.addr,
                         InTransitConfig(transport="scp_mem",
                                         tar_prefix="alt"))
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    sink.stage_array("field", x, step=0)
    sink.flush()
    got = SavimeClient(savime.addr).run("select(alt_field, v)")
    assert np.allclose(got[0], x)
    sink.close()
