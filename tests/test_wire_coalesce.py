"""Binary wire fast path + small-dataset coalescing (DESIGN.md §10).

Covers: property-based round-trips of the packed bin1 headers,
binary↔JSON negotiation fallback in both directions (old client vs new
server and vice versa), vectored scatter-gather sends, the receive
buffer pool, coalescer flush-on-size / flush-on-linger / flush-on-close,
batched reservation rollback on partial failure, end-to-end content
parity on every path combination, proactive credit pushes, and the
guard that the copy-emulation baselines never negotiate the binary path.
"""
from __future__ import annotations

import socket
import struct
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import wire
from repro.core.savime import SavimeServer
from repro.core.staging import StagingServer
from repro.transport import TransferSession, TransportConfig, create
from repro.transport.channels import ChannelGroup
from repro.transport.coalesce import Coalescer

settings.register_profile("ci", max_examples=50, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _stack(**kw):
    sv = SavimeServer().start()
    stg = StagingServer(sv.addr, mem_capacity=kw.pop("mem_capacity", 1 << 30),
                        **kw).start()
    return sv, stg


def _roundtrip(header, payload=None):
    a, b = socket.socketpair()
    try:
        wire.send_frame_bin(a, header, payload)
        return wire.recv_frame(b)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# packed-header round-trips (property-based)
# ---------------------------------------------------------------------------


@st.composite
def _hot_headers(draw):
    op = draw(st.sampled_from(["stripe", "reg_block", "ack", "credit"]))
    ident = "".join(f"{draw(st.integers(0, 255)):02x}"
                    for _ in range(draw(st.integers(1, 8))))
    if op == "stripe":
        h = {"op": "stripe", "file_id": ident,
             "stripe_idx": draw(st.integers(0, 1 << 31)),
             "n_stripes": draw(st.integers(0, 1 << 31)),
             "offset": draw(st.integers(0, 1 << 62))}
        if draw(st.sampled_from([0, 1])):
            h["sided"] = 1
            h["size"] = draw(st.integers(0, 1 << 62))
    elif op == "reg_block":
        h = {"op": "reg_block", "file_id": ident,
             "offset": draw(st.integers(0, 1 << 62)),
             "size": draw(st.integers(0, 1 << 62))}
    elif op == "ack":
        h = {"op": "ack", "ok": bool(draw(st.sampled_from([0, 1]))),
             "dup": bool(draw(st.sampled_from([0, 1]))),
             "done": bool(draw(st.sampled_from([0, 1]))),
             "stripe_idx": draw(st.integers(0, 1 << 31)),
             "credits": draw(st.integers(0, 1 << 31)),
             "offset": draw(st.integers(0, 1 << 62)),
             "size": draw(st.integers(0, 1 << 62))}
        if draw(st.sampled_from([0, 1])):
            h["rkey"] = ident
    else:
        h = {"op": "credit", "credits": draw(st.integers(0, 1 << 31))}
    return h


@given(header=_hot_headers(), nbytes=st.integers(0, 1 << 16))
def test_bin_header_roundtrip(header, nbytes):
    """Every hot op survives pack -> unpack with its semantic fields
    intact — including identifiers whose raw bytes end in 0x00 (the
    padding must not eat them)."""
    hb = wire.encode_bin_header(header, nbytes)
    assert hb is not None and len(hb) == wire.BIN_HEADER_LEN
    assert hb[0] == wire.BIN_MAGIC
    dec = wire.decode_bin_header(hb)
    assert dec.pop("_bin") is True
    assert dec.pop("nbytes") == nbytes
    for k, v in header.items():
        if header.get("op") == "ack" and k in ("ok", "dup", "done"):
            assert dec[k] == bool(v)
        elif k == "sided":
            assert dec[k] == 1
        else:
            assert dec[k] == v, (k, header, dec)


def test_bin_header_trailing_zero_id_exact():
    h = {"op": "stripe", "file_id": "ab00cd0000000000", "stripe_idx": 1,
         "n_stripes": 2, "offset": 0}
    dec = wire.decode_bin_header(wire.encode_bin_header(h, 0))
    assert dec["file_id"] == "ab00cd0000000000"


def test_bin_header_falls_back_for_non_hot_ops():
    assert wire.encode_bin_header({"op": "write_req", "size": 4}, 0) is None
    assert wire.encode_bin_header({"op": "batch_open", "items": []}, 0) is None
    # oversized identifier cannot ride the fixed layout either
    assert wire.encode_bin_header(
        {"op": "stripe", "file_id": "ab" * 9, "stripe_idx": 0,
         "n_stripes": 1, "offset": 0}, 0) is None


def test_bin_version_and_magic_rejected():
    hb = bytearray(wire.encode_bin_header(
        {"op": "credit", "credits": 1}, 0))
    hb[1] = 99                                 # unsupported version
    with pytest.raises(wire.ProtocolError, match="version"):
        wire.decode_bin_header(bytes(hb))
    hb[1] = wire.BIN_VERSION
    hb[2] = 200                                # unknown op
    with pytest.raises(wire.ProtocolError, match="unknown binary op"):
        wire.decode_bin_header(bytes(hb))


def test_bin_error_ack_carries_message_as_payload():
    h, _ = _roundtrip({"op": "ack", "ok": False, "error": "kaboom"})
    assert h["ok"] is False and h["error"] == "kaboom"


def test_bin_and_json_frames_interleave_on_one_stream():
    a, b = socket.socketpair()
    try:
        wire.send_frame_bin(a, {"op": "stripe", "file_id": "aa" * 8,
                                "stripe_idx": 0, "n_stripes": 1,
                                "offset": 0}, b"pay")
        wire.send_frame(a, {"op": "stats"})
        wire.send_frame_bin(a, {"op": "credit", "credits": 3})
        h1, p1 = wire.recv_frame(b)
        h2, _ = wire.recv_frame(b)
        h3, _ = wire.recv_frame(b)
        assert h1["op"] == "stripe" and bytes(p1) == b"pay"
        assert h2 == {"op": "stats", "nbytes": 0}
        assert h3["op"] == "credit" and h3["credits"] == 3
    finally:
        a.close()
        b.close()


def test_legacy_json_frame_bytes_identical():
    """wire_format=json must stay byte-identical to the pre-bin1 wire."""
    a, b = socket.socketpair()
    try:
        wire.send_frame(a, {"op": "ping"}, b"xy")
        import json
        hb = json.dumps({"op": "ping", "nbytes": 2}).encode()
        expect = struct.pack(">Q", len(hb)) + hb + b"xy"
        got = b.recv(1024)
        assert got == expect
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# vectored sends + buffer pool
# ---------------------------------------------------------------------------


def test_send_frames_vectored_parity_and_partial_sends():
    """Many frames (binary + JSON fallback, multi-buffer payloads) pushed
    through one vectored call arrive frame-for-frame identical, even when
    a tiny send buffer forces partial sendmsg continuation."""
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16 << 10)
    payload = np.arange(512 << 10, dtype=np.uint8)
    frames = [({"op": "stripe", "file_id": "ab" * 8, "stripe_idx": i,
                "n_stripes": 4, "offset": i * 100}, payload[i::4])
              for i in range(4)]
    frames.append(({"op": "batch_write", "count": 2},
                   [b"left", bytearray(b"right")]))
    frames.append(({"op": "credit", "credits": 9}, None))
    got = []
    rx = threading.Thread(
        target=lambda: [got.append(wire.recv_frame(b)) for _ in frames])
    rx.start()
    # non-contiguous numpy slices are not iovec-able; hand contiguous ones
    contiguous = [(h, np.ascontiguousarray(p) if isinstance(p, np.ndarray)
                   else p) for h, p in frames]
    n = wire.send_frames_vectored(a, contiguous, fmt=wire.WIRE_BIN1)
    rx.join(10)
    assert n == len(frames) and len(got) == len(frames)
    for (h, p), (rh, rp) in zip(contiguous, got):
        assert rh["op"] == h["op"]
        if h["op"] == "stripe":
            assert bytes(rp) == p.tobytes()
    assert bytes(got[4][1]) == b"leftright"
    assert got[5][0]["credits"] == 9
    a.close()
    b.close()


def test_buffer_pool_reuses_released_buffers():
    pool = wire.BufferPool(max_per_bucket=2)
    v1 = pool.acquire(1000)
    assert len(v1) == 1000
    backing = v1.obj
    pool.release(v1)
    v2 = pool.acquire(900)          # same pow2 bucket (1024)
    assert v2.obj is backing
    # unreleased leases degrade to plain allocation, never corruption
    v3 = pool.acquire(900)
    assert v3.obj is not backing
    # bucket bound holds
    pool.release(v2)
    pool.release(v3)
    extra = pool.acquire(900)
    pool.release(extra)
    assert len(pool._buckets[1024]) <= 2


def test_recv_header_uses_scratch_not_fresh_allocations():
    """Headers of any size parse from the per-thread scratch buffer; the
    old double-materialization (bytes(bytearray)) is gone, behavior is
    unchanged."""
    a, b = socket.socketpair()
    try:
        big = {"op": "x", "blob": "y" * 5000}
        wire.send_frame(a, big)
        wire.send_frame(a, {"op": "small"})
        h1 = wire.recv_header(b)
        assert h1["blob"] == "y" * 5000
        wire.drain_payload(b, h1)
        assert wire.recv_header(b)["op"] == "small"
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# negotiation (both fallback directions)
# ---------------------------------------------------------------------------


class _PreBin1StagingServer(StagingServer):
    """A server from before this PR: hello is an unknown op."""

    def _handle(self, h, payload):
        if h.get("op") == "hello":
            raise ValueError(f"unknown op {h.get('op')!r}")
        return super()._handle(h, payload)


def test_negotiate_agrees_bin1_with_new_server():
    sv, stg = _stack()
    try:
        sock = wire.connect(stg.addr)
        assert wire.negotiate(sock) == wire.WIRE_BIN1
        assert wire.negotiated(sock) == wire.WIRE_BIN1
        sock.close()
    finally:
        stg.stop()
        sv.stop()


def test_new_client_vs_old_server_falls_back_to_json():
    """bin1-preferring client against a pre-handshake server: the unknown
    hello op *is* the negotiation — everything stays on JSON and the
    transfer still lands."""
    sv = SavimeServer().start()
    stg = _PreBin1StagingServer(sv.addr, mem_capacity=1 << 30).start()
    try:
        data = np.arange(4096, dtype=np.float64)
        cfg = TransportConfig(staging_addr=stg.addr, wire_format="bin1",
                              block_size=8 << 10)
        with TransferSession("rdma_staged", cfg) as sess:
            sess.write("fallback", data, dtype="float64")
            sess.sync()
            sess.drain()
        assert stg.stats["bin_conns"] == 0
        assert np.array_equal(sv.engine.datasets["fallback"], data)
    finally:
        stg.stop()
        sv.stop()


def test_old_client_vs_new_server_stays_json():
    """A client that never sends hello (wire_format=json is the default)
    speaks the byte-identical legacy protocol against the new server."""
    sv, stg = _stack()
    try:
        data = np.arange(2048, dtype=np.float64)
        cfg = TransportConfig(staging_addr=stg.addr, block_size=8 << 10)
        assert cfg.wire_format == "json" and cfg.coalesce_bytes == 0
        with TransferSession("rdma_staged", cfg) as sess:
            sess.write("legacy", data, dtype="float64")
            sess.sync()
            sess.drain()
        assert stg.stats["bin_conns"] == 0
        assert stg.stats["batches"] == 0
        assert np.array_equal(sv.engine.datasets["legacy"], data)
    finally:
        stg.stop()
        sv.stop()


def test_binary_block_and_striped_paths_end_to_end():
    sv, stg = _stack()
    try:
        bufs = {f"d{i}": np.random.default_rng(i).standard_normal(4096)
                for i in range(6)}
        # block path (n_channels=1): reg_block/ack ride bin1
        cfg = TransportConfig(staging_addr=stg.addr, wire_format="bin1",
                              block_size=8 << 10)
        with TransferSession("rdma_staged", cfg) as sess:
            for n, b in bufs.items():
                sess.write(n, b, dtype="float64")
            sess.sync()
            sess.drain()
        # striped path: stripe/ack frames ride bin1 on every channel
        cfg2 = cfg.replace(n_channels=2, stripe_bytes=8 << 10)
        with TransferSession("rdma_staged", cfg2) as sess:
            for n, b in bufs.items():
                sess.write("s" + n, b, dtype="float64")
            sess.sync()
            sess.drain()
        assert stg.stats["bin_conns"] >= 2       # both data channels
        for n, b in bufs.items():
            assert np.array_equal(sv.engine.datasets[n], b)
            assert np.array_equal(sv.engine.datasets["s" + n], b)
    finally:
        stg.stop()
        sv.stop()


# ---------------------------------------------------------------------------
# coalescer unit behavior
# ---------------------------------------------------------------------------


class _Recorder:
    def __init__(self, fail=False):
        self.batches = []
        self.fail = fail
        self.event = threading.Event()

    def __call__(self, items):
        self.batches.append(items)
        self.event.set()
        if self.fail:
            raise RuntimeError("flush exploded")


def _add(c, name, n=1024):
    return c.add(name, "uint8", np.zeros(n, dtype=np.uint8), n)


def test_coalescer_flush_on_size():
    rec = _Recorder()
    c = Coalescer(rec, coalesce_bytes=4096, linger_ms=10_000)
    try:
        handles = [_add(c, f"a{i}", 1024) for i in range(4)]  # == threshold
        assert rec.event.wait(5)
        for h in handles:
            assert h.wait(5) == 1024
        assert len(rec.batches) == 1 and len(rec.batches[0]) == 4
    finally:
        c.close()


def test_coalescer_flush_on_linger():
    rec = _Recorder()
    c = Coalescer(rec, coalesce_bytes=1 << 30, linger_ms=30)
    try:
        t0 = time.monotonic()
        h = _add(c, "lone", 64)
        h.wait(5)
        elapsed = time.monotonic() - t0
        # flushed by the linger window, not size and not immediately
        assert 0.02 <= elapsed < 5
        assert len(rec.batches) == 1
    finally:
        c.close()


def test_coalescer_flush_on_close():
    rec = _Recorder()
    c = Coalescer(rec, coalesce_bytes=1 << 30, linger_ms=60_000)
    h = _add(c, "tail", 64)
    c.close()
    assert h.done.is_set() and h.error is None
    assert len(rec.batches) == 1


def test_coalescer_sync_flushes_and_failure_reaches_handles():
    rec = _Recorder(fail=True)
    c = Coalescer(rec, coalesce_bytes=1 << 30, linger_ms=60_000)
    try:
        handles = [_add(c, f"f{i}") for i in range(3)]
        c.sync(5)
        for h in handles:
            with pytest.raises(RuntimeError, match="flush exploded"):
                h.wait(1)
    finally:
        c.close()


def test_coalescer_rejects_adds_after_close():
    c = Coalescer(_Recorder(), coalesce_bytes=1024)
    c.close()
    with pytest.raises(RuntimeError, match="closed"):
        _add(c, "late")


# ---------------------------------------------------------------------------
# batched reservations: rollback + end-to-end coalescing
# ---------------------------------------------------------------------------


def test_batch_open_rollback_on_partial_failure(monkeypatch):
    """If the Nth reservation of a batch fails, every earlier one is
    released (capacity and regions) and the connection stays framed."""
    import repro.core.staging as staging_mod
    sv, stg = _stack()
    real_region = staging_mod.MemoryRegion
    made = []

    class Flaky(real_region):
        def __init__(self, *a, **kw):
            if len(made) == 2:          # third region creation explodes
                made.append("boom")
                raise OSError("synthetic mmap failure")
            made.append(a[0] if a else kw.get("path"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(staging_mod, "MemoryRegion", Flaky)
    try:
        sock = wire.connect(stg.addr)
        items = [{"name": f"x{i}", "dtype": "uint8", "size": 1 << 20}
                 for i in range(5)]
        h, _ = wire.request(sock, {"op": "batch_open", "items": items})
        assert h["ok"] is False and "rolled back" in h["error"]
        stats, _ = wire.request(sock, {"op": "stats"})
        assert stats["mem_used"] == 0 and stats["queued"] == 0
        # a batch_write after the failed open is rejected but must not
        # desynchronize the stream (its payload is drained)
        wire.send_frame(sock, {"op": "batch_write", "count": 5},
                        b"z" * 64)
        h2, _ = wire.recv_frame(sock)
        assert h2["ok"] is False and "batch_open" in h2["error"]
        ping, _ = wire.request(sock, {"op": "ping"})
        assert ping["ok"] is True
        # and the server still accepts healthy batches afterwards
        monkeypatch.setattr(staging_mod, "MemoryRegion", real_region)
        h3, _ = wire.request(sock, {"op": "batch_open", "items": items[:2]})
        assert h3["ok"] is True and len(h3["items"]) == 2
        wire.send_frame(sock, {"op": "batch_write", "count": 2},
                        b"q" * (2 << 20))
        h4, _ = wire.recv_frame(sock)
        assert h4["ok"] is True and h4["count"] == 2
        sock.close()
    finally:
        stg.stop()
        sv.stop()


def test_batch_open_reservations_released_on_disconnect():
    """A client that dies between batch_open and batch_write must not
    leak its reservations: leaked bytes would permanently shrink every
    future credit grant (the stripe TTL reaper does not cover them)."""
    sv, stg = _stack(mem_capacity=1 << 24)
    try:
        sock = wire.connect(stg.addr)
        items = [{"name": f"d{i}", "dtype": "uint8", "size": 1 << 20}
                 for i in range(4)]
        h, _ = wire.request(sock, {"op": "batch_open", "items": items})
        assert h["ok"] and len(h["items"]) == 4
        sock.close()                       # vanish before batch_write
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with stg._alloc_lock:
                used = stg._mem_used
            if used == 0:
                break
            time.sleep(0.02)
        assert used == 0, "abandoned batch reservations leaked"
        with stg._ds_lock:
            assert not stg._datasets
        # a second batch_open on one conn abandons the first unconsumed one
        sock = wire.connect(stg.addr)
        wire.request(sock, {"op": "batch_open", "items": items[:2]})
        wire.request(sock, {"op": "batch_open", "items": items[:1]})
        stats, _ = wire.request(sock, {"op": "stats"})
        assert stats["mem_used"] == 1 << 20   # only the live batch remains
        sock.close()
    finally:
        stg.stop()
        sv.stop()


def test_coalesced_small_datasets_land_with_content_parity():
    sv, stg = _stack()
    try:
        rng = np.random.default_rng(7)
        bufs = {f"tiny{i}": rng.standard_normal(1024) for i in range(24)}
        bufs["empty"] = np.zeros(0, dtype=np.float64)
        big = rng.standard_normal(1 << 18)       # 2 MiB: bypasses
        cfg = TransportConfig(staging_addr=stg.addr, wire_format="bin1",
                              coalesce_bytes=256 << 10, linger_ms=50,
                              block_size=1 << 20)
        with TransferSession("rdma_staged", cfg) as sess:
            for n, b in bufs.items():
                sess.write(n, b, dtype="float64")
            sess.write("big", big, dtype="float64")
            sess.sync()
            sess.drain()
        assert stg.stats["batches"] >= 1
        assert stg.stats["batched_datasets"] == len(bufs)
        assert stg.stats["datasets"] == len(bufs) + 1
        for n, b in bufs.items():
            assert np.array_equal(sv.engine.datasets[n], b), n
        assert np.array_equal(sv.engine.datasets["big"], big)
    finally:
        stg.stop()
        sv.stop()


def test_coalesce_zero_is_legacy_path():
    """coalesce_bytes=0 (default) must not even build a coalescer."""
    sv, stg = _stack()
    try:
        cfg = TransportConfig(staging_addr=stg.addr)
        t = create("rdma_staged", cfg)
        t.open()
        try:
            assert t.comm._coalescer is None
        finally:
            t.close()
    finally:
        stg.stop()
        sv.stop()


# ---------------------------------------------------------------------------
# proactive credit frames
# ---------------------------------------------------------------------------


class _CreditPushServer:
    """Stripe endpoint that pushes an unsolicited binary credit frame
    before acking (acks deliberately carry no credits)."""

    def __init__(self):
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(8)
        self.addr = f"127.0.0.1:{self._srv.getsockname()[1]}"
        self._stop = threading.Event()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        with conn:
            while True:
                try:
                    h, _ = wire.recv_frame(conn)
                except (ConnectionError, OSError):
                    return
                try:
                    if h.get("op") == "hello":
                        wire.send_frame(conn, wire.hello_reply(h))
                    elif h.get("op") == "stripe_open":
                        wire.send_frame(conn, {"ok": True, "file_id": "f1",
                                               "credits": 2})
                    else:
                        wire.send_frame_bin(conn, {"op": "credit",
                                                   "credits": 7})
                        wire.send_frame(conn, {"ok": True,
                                               "stripe_idx":
                                                   h.get("stripe_idx"),
                                               "done": False, "dup": False})
                except OSError:
                    return

    def stop(self):
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass


def test_unsolicited_credit_frame_updates_window_without_eating_acks():
    srv = _CreditPushServer()
    group = ChannelGroup(srv.addr, n_channels=1, stripe_bytes=1 << 10,
                         credits=4, wire_format="bin1").open()
    try:
        assert group.wire_format == "bin1"
        group.send_dataset("w", "uint8", np.zeros(4 << 10, dtype=np.uint8),
                           timeout=20)
        stats = group.channel_stats()[0]
        # every stripe was acked (no credit frame consumed an ack slot)
        # and the pushed grant became the window
        assert stats["n_stripes"] == 4
        assert stats["window"] == 7
    finally:
        group.close()
        srv.stop()


def test_staging_pushes_credits_to_bin_channels():
    """A forward to SAVIME that releases staging memory proactively
    raises bin1 channel windows (credit_pushes > 0 on the server)."""
    sv, stg = _stack(mem_capacity=1 << 22)
    try:
        cfg = TransportConfig(staging_addr=stg.addr, wire_format="bin1",
                              n_channels=2, stripe_bytes=64 << 10,
                              block_size=64 << 10, credits=4)
        data = np.random.default_rng(0).standard_normal(1 << 16)
        with TransferSession("rdma_staged", cfg) as sess:
            for i in range(4):
                sess.write(f"p{i}", data, dtype="float64")
            sess.sync()
            sess.drain()
        assert stg.stats["credit_pushes"] > 0
    finally:
        stg.stop()
        sv.stop()


# ---------------------------------------------------------------------------
# baseline guard: the copy emulations never go binary
# ---------------------------------------------------------------------------


def test_channelgroup_with_custom_send_frame_never_negotiates_binary():
    def fake_send_frame(sock, header, payload=None):  # pragma: no cover
        wire.send_frame(sock, header, payload)

    g = ChannelGroup("127.0.0.1:1", n_channels=1,
                     send_frame=fake_send_frame, wire_format="bin1")
    assert g.wire_format == "json"       # pinned before any connection


@pytest.mark.parametrize("engine", ["scp_mem", "ssh_direct"])
def test_copy_emulation_transports_never_negotiate_binary(engine):
    """The scp/ssh engines are the paper's measured baselines: even when
    the config begs for bin1 + coalescing they must keep the JSON wire
    and their per-dataset copy cost model."""
    sv = SavimeServer().start()
    try:
        cfg = TransportConfig(savime_addr=sv.addr, wire_format="bin1",
                              coalesce_bytes=1 << 20, n_channels=2,
                              stripe_bytes=16 << 10, io_threads=1,
                              block_size=64 << 10)
        t = create(engine, cfg)
        t.open()
        try:
            assert t._group is not None
            assert t._group.wire_format == "json"
            data = np.random.default_rng(1).standard_normal(8192)
            t.write("guard", "float64", data).wait(30)
            t.sync(30)
            t.drain(30)
        finally:
            t.close()
        assert np.array_equal(sv.engine.datasets["guard"], data)
    finally:
        sv.stop()
