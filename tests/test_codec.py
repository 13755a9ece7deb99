"""Negotiated egress reduction codecs (DESIGN.md §13).

Covers: per-codec property round-trips (lossless codecs byte-exact,
int8-block within its scale/2 error bound, empty payloads, sizes off the
4096-element block grid), delta-rle chain semantics (ordering, reset on
size change, out-of-order parking at the server), codec↔no-codec hello
negotiation fallback in both directions, byte-identity of the default
``codec="none"`` path, end-to-end content parity for ingest and lazy
query-time decode (flat and paged staging), accounting parity between
client ``codec_stats`` and server counters, and the guard that the
copy-emulation baselines are structurally pinned to raw bytes.
"""
from __future__ import annotations

import socket
import threading

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import codec as codec_mod
from repro.core import wire
from repro.core.client import Communicator
from repro.core.savime import SavimeServer
from repro.core.staging import StagingServer
from repro.transport import TransferSession, TransportConfig

settings.register_profile("ci", max_examples=50, deadline=None)
settings.load_profile("ci")

BLOCK = 4096


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _rand_bytes(rng: np.random.Generator, n: int, sparse: bool) -> np.ndarray:
    if sparse:
        buf = np.zeros(n, np.uint8)
        if n:
            k = max(1, n // 50)
            idx = rng.integers(0, n, k)
            buf[idx] = rng.integers(1, 255, k)
        return buf
    return rng.integers(0, 256, n, dtype=np.uint8,
                        endpoint=False).astype(np.uint8)


@st.composite
def _byte_payload(draw):
    n = draw(st.sampled_from(
        [0, 1, 7, 64, 65, 4096, 4097, 3 * 4096 + 13, 100_000]))
    sparse = draw(st.sampled_from([True, False]))
    seed = draw(st.integers(0, 2 ** 16))
    return _rand_bytes(np.random.default_rng(seed), n, sparse)


# ---------------------------------------------------------------------------
# per-codec properties
# ---------------------------------------------------------------------------


def test_registry_has_the_three_codecs():
    names = codec_mod.available()
    for name in ("none", "delta-rle", "int8-block"):
        assert name in names
    with pytest.raises(codec_mod.UnknownCodecError):
        codec_mod.get("zstd-unheard-of")
    # create() returns fresh instances: chain state must not be shared
    assert codec_mod.create("delta-rle") is not codec_mod.create("delta-rle")


@given(name=st.sampled_from(["none", "delta-rle", "int8-block"]),
       buf=_byte_payload())
def test_uint8_roundtrip_byte_exact(name, buf):
    # uint8 payloads must round-trip exactly through every codec —
    # int8-block passes non-float dtypes through rather than corrupt them
    enc = codec_mod.create(name)
    dec = codec_mod.create(name)
    payload, meta = enc.encode(buf, dtype="uint8", key="k")
    out = dec.decode(payload, meta, key="k")
    np.testing.assert_array_equal(np.asarray(out, np.uint8).reshape(-1), buf)


@given(seed=st.integers(0, 2 ** 16),
       n=st.sampled_from([0, 1, BLOCK - 1, BLOCK, BLOCK + 1,
                          2 * BLOCK + 300, 50_000]),
       dtype=st.sampled_from(["float32", "float64", "float16"]))
def test_int8_block_error_bound(seed, n, dtype):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) *
         10.0 ** float(rng.integers(-2, 3))).astype(dtype)
    c = codec_mod.create("int8-block")
    payload, meta = c.encode(x.view(np.uint8), dtype=dtype, key="k")
    out = np.asarray(codec_mod.create("int8-block").decode(
        payload, meta, key="k")).view(dtype)
    assert out.shape == x.shape
    if n == 0:
        return
    nb = -(-n // BLOCK)
    scales = np.asarray(codec_mod.as_bytes_array(payload))[
        :nb * 4].view(np.float32)
    bound = np.repeat(scales, BLOCK)[:n] * 0.5
    # float16 storage adds half-ulp on both legs of the round-trip
    slack = np.finfo(dtype).eps * (np.abs(x.astype(np.float64)) + 1)
    err = np.abs(out.astype(np.float64) - x.astype(np.float64))
    assert (err <= bound + slack + 1e-12).all()
    # and the payload actually shrank (scales + int8 vs full floats)
    raw = codec_mod.as_bytes_array(payload)
    if n >= BLOCK:
        assert raw.size < x.nbytes


@given(seed=st.integers(0, 2 ** 16),
       n=st.sampled_from([1, 64, 4096, 100_000]),
       steps=st.integers(2, 5))
def test_delta_rle_chain_roundtrip(seed, n, steps):
    rng = np.random.default_rng(seed)
    enc = codec_mod.create("delta-rle")
    dec = codec_mod.create("delta-rle")
    buf = _rand_bytes(rng, n, sparse=False)
    total_wire = 0
    for _ in range(steps):
        # sparse perturbation: the xor-delta is mostly zeros
        buf = buf.copy()
        k = max(1, n // 100)
        buf[rng.integers(0, n, k)] ^= 0xA5
        payload, meta = enc.encode(buf, dtype="uint8", key="d")
        total_wire += int(codec_mod.as_bytes_array(payload).size)
        out = dec.decode(payload, meta, key="d")
        np.testing.assert_array_equal(
            np.asarray(out, np.uint8).reshape(-1), buf)
    if n >= 4096:
        assert total_wire < steps * n   # deltas compressed


def test_delta_rle_resets_on_size_change_and_tracks_keys():
    enc = codec_mod.create("delta-rle")
    dec = codec_mod.create("delta-rle")
    a = np.arange(100, dtype=np.uint8)
    b = np.arange(200, dtype=np.uint8)
    for buf, key in ((a, "x"), (b, "y"), (b[:100], "x"), (a, "y")):
        p, m = enc.encode(buf.copy(), dtype="uint8", key=key)
        out = dec.decode(p, m, key=key)
        np.testing.assert_array_equal(np.asarray(out).reshape(-1), buf)
    # size change resets the chain: base must be None again
    p, m = enc.encode(np.zeros(77, np.uint8), dtype="uint8", key="x")
    assert m["base"] is None


def test_delta_rle_out_of_order_decode_raises():
    enc = codec_mod.create("delta-rle")
    p1, m1 = enc.encode(np.zeros(64, np.uint8), dtype="uint8", key="k")
    p2, m2 = enc.encode(np.ones(64, np.uint8), dtype="uint8", key="k")
    dec = codec_mod.create("delta-rle")
    with pytest.raises(codec_mod.CodecOrderError) as ei:
        dec.decode(p2, m2, key="k")
    assert ei.value.base == m2["base"]
    # delivering the base first unblocks the chain
    dec.decode(p1, m1, key="k")
    out = dec.decode(p2, m2, key="k")
    np.testing.assert_array_equal(np.asarray(out).reshape(-1),
                                  np.ones(64, np.uint8))


# ---------------------------------------------------------------------------
# hello negotiation
# ---------------------------------------------------------------------------


def _hello_server(reply_codecs):
    """One-shot hello server; returns (addr, captured_offers, thread)."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    offers = []

    def run():
        conn, _ = srv.accept()
        h, _ = wire.recv_frame(conn)
        offers.append(h)
        wire.send_frame(conn, wire.hello_reply(h, codecs=reply_codecs))
        conn.recv(1)   # linger until the client closes
        conn.close()
        srv.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return srv.getsockname(), offers, t


def test_negotiate_codec_accepted_when_both_sides_support():
    addr, offers, t = _hello_server(("delta-rle", "int8-block", "none"))
    sock = socket.create_connection(addr)
    wire.negotiate(sock, codecs=("int8-block",))
    assert wire.negotiated_codecs(sock) == ("int8-block",)
    assert offers[0].get("codecs") == ["int8-block"]
    sock.close()
    t.join(2)


def test_negotiate_codec_vs_old_server_falls_back():
    # "old server": replies without a codecs field at all
    addr, offers, t = _hello_server(())
    sock = socket.create_connection(addr)
    wire.negotiate(sock, codecs=("int8-block",))
    assert wire.negotiated_codecs(sock) == ()
    sock.close()
    t.join(2)


def test_old_client_offer_has_no_codecs_field():
    # codec="none" must be wire-byte-identical to the pre-codec client:
    # the hello offer carries no codecs key, and the reply omits it too
    addr, offers, t = _hello_server(("delta-rle",))
    sock = socket.create_connection(addr)
    wire.negotiate(sock)
    assert "codecs" not in offers[0]
    assert wire.negotiated_codecs(sock) == ()
    sock.close()
    t.join(2)
    assert "codecs" not in wire.hello_reply({"op": "hello"},
                                            codecs=("delta-rle",))


# ---------------------------------------------------------------------------
# end-to-end through staging
# ---------------------------------------------------------------------------


@pytest.fixture()
def stack():
    sav = SavimeServer()
    sav.start()
    st_ = StagingServer(sav.addr, mem_capacity=1 << 26).start()
    yield sav, st_
    st_.stop()
    sav.stop()


@pytest.mark.parametrize("wire_format", ["json", "bin1"])
def test_e2e_int8_block_ingest_decode(stack, wire_format):
    sav, st_ = stack
    comm = Communicator(st_.addr, 1, 1 << 20, wire_format=wire_format,
                        codec="int8-block")
    x = np.linspace(-3, 3, 5000, dtype=np.float32)
    comm.submit("d1", "float", x.view(np.uint8))
    comm.sync()
    st_.drain(5)
    got = np.frombuffer(sav.engine.datasets["d1"], dtype=np.float32)
    assert np.abs(got - x).max() <= np.abs(x).max() / 254 + 1e-7
    cs = comm.codec_stats()
    assert cs["wire_bytes"] < cs["raw_bytes"] == x.nbytes
    assert cs["fallbacks"] == 0
    # accounting parity: server bytes_in counts wire bytes, raw_bytes_in
    # the decoded size, and the SAVIME hop ships raw bytes
    assert st_.stats["bytes_in"] == cs["wire_bytes"]
    assert st_.stats["raw_bytes_in"] == x.nbytes
    assert st_.stats["bytes_to_savime"] == x.nbytes
    assert st_.stats["codec_datasets"] == 1
    comm.stop()


def test_e2e_codec_none_default_is_raw(stack):
    sav, st_ = stack
    comm = Communicator(st_.addr, 1, 1 << 20)
    x = np.arange(4000, dtype=np.float32)
    comm.submit("plain", "float", x.view(np.uint8))
    comm.sync()
    st_.drain(5)
    got = np.frombuffer(sav.engine.datasets["plain"], dtype=np.float32)
    np.testing.assert_array_equal(got, x)
    assert comm.codec_stats() == {}
    assert st_.stats["bytes_in"] == x.nbytes
    assert st_.stats["raw_bytes_in"] == x.nbytes   # raw == wire, no codec
    assert st_.stats["codec_datasets"] == 0
    comm.stop()


def test_e2e_codec_vs_non_advertising_server_ships_raw(stack, monkeypatch):
    # "old server" emulation: the staging hello stops advertising codecs;
    # a codec-configured client must silently fall back to raw bytes
    sav, st_ = stack
    monkeypatch.setattr(codec_mod, "available", lambda: ())
    comm = Communicator(st_.addr, 1, 1 << 20, wire_format="bin1",
                        codec="int8-block")
    x = np.linspace(0, 1, 3000, dtype=np.float64)
    comm.submit("raw1", "double", x.view(np.uint8))
    comm.sync()
    st_.drain(5)
    got = np.frombuffer(sav.engine.datasets["raw1"], dtype=np.float64)
    np.testing.assert_array_equal(got, x)     # byte-exact: nothing encoded
    cs = comm.codec_stats()
    assert cs["fallbacks"] == 1 and cs["datasets"] == 0
    assert st_.stats["bytes_in"] == x.nbytes
    comm.stop()


def test_e2e_delta_rle_chain_and_query_decode(stack):
    sav, st_ = stack
    comm = Communicator(st_.addr, 1, 1 << 20, wire_format="bin1",
                        codec="delta-rle")
    buf = np.zeros(50_000, np.uint8)
    for i in range(4):
        buf = buf.copy()
        buf[i * 7] = i + 1
        comm.submit("chain", "uint8", buf)
        comm.sync()
    st_.drain(5)
    got = np.frombuffer(sav.engine.datasets["chain"], dtype=np.uint8)
    np.testing.assert_array_equal(got, buf)
    cs = comm.codec_stats()
    assert cs["wire_bytes"] < cs["raw_bytes"]
    comm.stop()

    # decode_at="query": stored compressed, decoded on the forward hop
    comm2 = Communicator(st_.addr, 1, 1 << 20, wire_format="bin1",
                         codec="int8-block", decode_at="query")
    y = np.sin(np.arange(20_000, dtype=np.float64))
    comm2.submit("lazy", "double", y.view(np.uint8))
    comm2.sync()
    st_.drain(5)
    got2 = np.frombuffer(sav.engine.datasets["lazy"], dtype=np.float64)
    assert np.abs(got2 - y).max() <= 1.0 / 254 + 1e-9
    assert st_.stats["bytes_to_savime"] >= y.nbytes   # raw on the last hop
    comm2.stop()


def test_e2e_query_decode_composes_with_paged_store():
    sav = SavimeServer()
    sav.start()
    st_ = StagingServer(sav.addr, mem_capacity=1 << 24,
                        page_bytes=1 << 16, dedup=True).start()
    comm = Communicator(st_.addr, 1, 1 << 20, wire_format="bin1",
                        codec="int8-block", decode_at="query")
    y = np.cos(np.arange(50_000, dtype=np.float64))
    comm.submit("pq", "double", y.view(np.uint8))
    comm.sync()
    st_.drain(5)
    got = np.frombuffer(sav.engine.datasets["pq"], dtype=np.float64)
    assert np.abs(got - y).max() <= 1.0 / 254 + 1e-9
    comm.stop()
    st_.stop()
    sav.stop()


def _deliver_inprocess(st_, name, payload, cinfo):
    """Land one pre-encoded dataset via the server's own op methods
    (deterministic arrival order — no client threads involved)."""
    pv = codec_mod.as_bytes_array(payload)
    rep = st_._op_write_req(dict(
        {"op": "write_req", "name": name, "dtype": "uint8",
         "size": int(pv.size)}, **cinfo))
    ds = st_._datasets[rep["file_id"]]
    off = 0
    for seg in ds.region.segments(0, ds.nbytes):
        ln = int(getattr(seg, "nbytes", None) or len(seg))
        seg[:] = pv[off:off + ln]
        off += ln
    st_._finish_dataset(ds)


def test_server_parks_out_of_order_chain_links(stack):
    # striping/io_threads can reorder chained datasets; the server must
    # park the successor until its base lands, then forward both in order
    sav, st_ = stack
    enc = codec_mod.create("delta-rle")
    b1 = np.zeros(8192, np.uint8)
    b2 = b1.copy()
    b2[7] = 99
    p1, m1 = enc.encode(b1, dtype="uint8", key="ooo")
    p2, m2 = enc.encode(b2, dtype="uint8", key="ooo")

    def cinfo(m, raw):
        return {"codec": "delta-rle", "cmeta": m, "raw_size": raw,
                "decode_at": "staging"}

    _deliver_inprocess(st_, "ooo", p2, cinfo(m2, b2.nbytes))   # out of order
    assert st_.stats["codec_parked"] == 1
    assert st_.stats["codec_datasets"] == 0
    _deliver_inprocess(st_, "ooo", p1, cinfo(m1, b1.nbytes))   # base arrives
    st_.drain(5)
    assert st_.stats["codec_datasets"] == 2
    got = np.frombuffer(sav.engine.datasets["ooo"], dtype=np.uint8)
    np.testing.assert_array_equal(got, b2)    # last write wins, in order


def test_write_req_rejects_unknown_codec(stack):
    _, st_ = stack
    with pytest.raises(codec_mod.UnknownCodecError):
        st_._op_write_req({"op": "write_req", "name": "x", "dtype": "uint8",
                           "size": 10, "codec": "nope"})
    # nothing reserved: a bad codec must not leak capacity
    assert st_._mem_used == 0 and not st_._datasets


# ---------------------------------------------------------------------------
# transport / session / baselines
# ---------------------------------------------------------------------------


def test_session_surfaces_codec_stats():
    sav = SavimeServer()
    sav.start()
    cfg = TransportConfig(savime_addr=sav.addr, wire_format="bin1",
                          codec="int8-block", mem_capacity=1 << 26)
    x = np.linspace(-1, 1, 9000, dtype=np.float32)
    with TransferSession("rdma_staged", cfg) as sess:
        sess.write("s1", x.view(np.uint8), dtype="float")
        sess.sync()
        sess.drain()
    stats = sess.stats
    assert stats.codec["name"] == "int8-block"
    assert 0 < stats.codec["wire_bytes"] < stats.codec["raw_bytes"]
    merged = type(stats).merge([stats, stats])
    assert merged.codec["raw_bytes"] == 2 * stats.codec["raw_bytes"]
    sav.stop()


@pytest.mark.parametrize("engine", ["scp_mem", "ssh_direct"])
def test_copy_baselines_are_pinned_to_raw(engine):
    # the baselines never touch the Communicator: cfg.codec is inert and
    # data lands byte-exact whatever codec the config asks for
    sav = SavimeServer()
    sav.start()
    cfg = TransportConfig(savime_addr=sav.addr, codec="int8-block",
                          mem_capacity=1 << 26)
    x = np.linspace(-2, 2, 6000, dtype=np.float32)
    with TransferSession(engine, cfg) as sess:
        sess.write("b1", x.view(np.uint8), dtype="float")
        sess.sync()
        sess.drain()
    got = np.frombuffer(sav.engine.datasets["b1"], dtype=np.float32)
    np.testing.assert_array_equal(got, x)     # byte-exact: no quantization
    assert sess.stats.codec == {}             # and no codec accounting
    sav.stop()


# ---------------------------------------------------------------------------
# grad_compress regression (mesh-free shape parity)
# ---------------------------------------------------------------------------


def test_grad_compress_flatten_pads_rows_to_pod_multiple():
    import jax.numpy as jnp
    from repro.optim import grad_compress as gc
    tree = {"w": jnp.zeros((2 * gc.QBLOCK + 5,)), "b": jnp.zeros((7,))}
    for n_pods in (1, 2, 3, 4):
        flat, pad = gc._flatten(tree, n_pods)
        assert flat.shape[0] % n_pods == 0
        assert flat.shape[0] * gc.QBLOCK == \
            (2 * gc.QBLOCK + 5 + 7) + pad
        err = gc.error_state(tree, n_pods)
        # the error buffer rides _pod_reduce's per-pod split: same rows
        assert err.shape == flat.shape
        back = gc._unflatten(flat, pad, tree)
        assert {k: v.shape for k, v in back.items()} == \
            {k: v.shape for k, v in tree.items()}
