"""Multi-tenant staging gateway (DESIGN.md §12).

Covers the whole subsystem: consistent-hash placement (unit + property
tests, including the exact only-moves-to-the-joiner invariant and
cross-process determinism), tenancy + typed quota rejections, stats
merge classmethods, StagingServer stop() hardening under health probes,
and the N=3 end-to-end acceptance scenario — ring-correct landing for
every ingest path, byte-identical scatter-gather parity with an N=1
run, backend failure remap with no lost acked datasets, and
gateway-vs-backend accounting parity.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.analysis.session import AnalysisStats
from repro.core import wire
from repro.core.savime import SavimeServer
from repro.core.staging import StagingServer
from repro.gateway import (AuthError, GatewayClient, GatewayServer,
                           QuotaExceededError, HashRing, RingNode,
                           RouterSession, StagingPool, Tenant, TenantRegistry,
                           error_from_reply, error_reply, merge_histograms)
from repro.transport import TransferSession, TransferStats, TransportConfig

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _nodes(n, weights=None):
    return [RingNode(f"b{i}", f"127.0.0.1:{9000 + i}",
                     weight=(weights[i] if weights else 1.0))
            for i in range(n)]


# ---------------------------------------------------------------------------
# ring units
# ---------------------------------------------------------------------------


def test_ring_placement_is_deterministic_and_total():
    r = HashRing(_nodes(3))
    for key in (f"ds{i}" for i in range(100)):
        assert r.place(key).name == r.place(key).name
        assert r.place(key).name in r
    # every node owns something at 64 vnodes / 100 keys
    owners = {r.place(f"ds{i}").name for i in range(100)}
    assert owners == {"b0", "b1", "b2"}


def test_ring_rejects_bad_input():
    with pytest.raises(ValueError):
        HashRing(_nodes(2) + [RingNode("b0", "x:1")])   # duplicate name
    with pytest.raises(ValueError):
        HashRing([RingNode("a", "x:1", weight=0.0)])    # nonpositive weight
    with pytest.raises(RuntimeError):
        HashRing([]).place("k")                          # empty ring


def test_ring_encode_decode_roundtrip_and_epoch():
    r = HashRing(_nodes(3, weights=[1.0, 2.0, 0.5]), vnodes=32)
    r2 = HashRing.decode(r.encode())
    assert r2.epoch == r.epoch
    assert [n.as_dict() for n in r2.nodes] == [n.as_dict() for n in r.nodes]
    for i in range(50):
        assert r.place(f"k{i}").name == r2.place(f"k{i}").name
    # epoch moves with membership, weights and vnodes
    assert r.with_node(RingNode("b9", "x:9")).epoch != r.epoch
    assert r.without_node("b1").epoch != r.epoch
    assert HashRing(r.nodes, vnodes=64).epoch != r.epoch
    # a tampered wire form is rejected, not silently adopted
    d = r.encode()
    d["nodes"][0]["weight"] = 9.0
    with pytest.raises(ValueError):
        HashRing.decode(d)


def test_ring_pure_membership_ops():
    r = HashRing(_nodes(3))
    grown = r.with_node(RingNode("b3", "127.0.0.1:9003"))
    assert len(r) == 3 and len(grown) == 4       # original untouched
    shrunk = grown.without_node("b0")
    assert "b0" in r and "b0" not in shrunk


def test_ring_cross_process_determinism():
    """Placement must not depend on PYTHONHASHSEED or process identity
    (BLAKE2b, not ``hash()``) — the client-side cached ring and the
    gateway must agree exactly."""
    keys = [f"ds{i}" for i in range(30)]
    r = HashRing(_nodes(3), vnodes=32)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = (
        "import sys, json; sys.path.insert(0, {src!r});"
        "from repro.gateway import HashRing, RingNode;"
        "r = HashRing([RingNode(f'b{{i}}', f'127.0.0.1:{{9000+i}}')"
        " for i in range(3)], vnodes=32);"
        "print(json.dumps([r.epoch] + [r.place(k).name for k in {keys!r}]))"
    ).format(src=src, keys=keys)
    env = dict(os.environ, PYTHONHASHSEED="12345")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, check=True)
    got = json.loads(out.stdout)
    assert got[0] == r.epoch
    assert got[1:] == [r.place(k).name for k in keys]


# ---------------------------------------------------------------------------
# ring properties (hypothesis)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=2, max_value=8),
       st.integers(min_value=0, max_value=10_000))
def test_ring_join_moves_keys_only_to_joiner(n, seed):
    """The consistent-hashing contract, exactly: adding a node may only
    move keys *onto* the new node, never between existing nodes."""
    r = HashRing(_nodes(n), vnodes=32)
    grown = r.with_node(RingNode("newbie", "127.0.0.1:9999"))
    keys = [f"k{seed}_{i}" for i in range(200)]
    moved = 0
    for k in keys:
        before, after = r.place(k).name, grown.place(k).name
        if before != after:
            assert after == "newbie"
            moved += 1
    # ≈ K/(N+1) expected; generous slack for hash variance at 32 vnodes
    assert moved <= len(keys) * 3.0 / (n + 1) + 10


@given(st.integers(min_value=3, max_value=8),
       st.integers(min_value=0, max_value=10_000))
def test_ring_leave_moves_only_the_leavers_keys(n, seed):
    r = HashRing(_nodes(n), vnodes=32)
    shrunk = r.without_node("b0")
    for i in range(200):
        k = f"k{seed}_{i}"
        before, after = r.place(k).name, shrunk.place(k).name
        if before != "b0":
            assert after == before    # survivors keep everything they had
        else:
            assert after != "b0"


@given(st.integers(min_value=0, max_value=10_000))
def test_ring_weights_shift_load_proportionally(seed):
    r = HashRing([RingNode("heavy", "x:1", weight=3.0),
                  RingNode("light", "x:2", weight=1.0)], vnodes=96)
    heavy = sum(r.place(f"k{seed}_{i}").name == "heavy" for i in range(600))
    # expectation 450/600; allow wide hash variance but require dominance
    assert 330 <= heavy <= 570


# ---------------------------------------------------------------------------
# tenancy
# ---------------------------------------------------------------------------


def test_tenant_auth_modes():
    reg = TenantRegistry([Tenant("acme", token="s3cret"),
                          Tenant("open-team")])
    assert reg.authenticate(None).name == "default"
    assert reg.authenticate("s3cret").name == "acme"
    assert reg.authenticate("open-team").name == "open-team"
    with pytest.raises(AuthError):
        reg.authenticate("acme")      # named tenant requires its token
    with pytest.raises(AuthError):
        reg.authenticate("nope")
    strict = TenantRegistry([Tenant("a", token="t")], require_auth=True)
    with pytest.raises(AuthError):
        strict.authenticate(None)


def test_tenant_quota_all_or_nothing():
    reg = TenantRegistry([Tenant("t", quota_bytes=100, quota_datasets=3)])
    reg.charge("t", 60)
    with pytest.raises(QuotaExceededError) as ei:
        reg.charge("t", 60)           # would cross the byte budget
    assert ei.value.tenant == "t"
    u = reg.usage("t")
    assert u == {"bytes": 60, "datasets": 1, "rejects": 1}   # no partial
    reg.charge("t", 10, datasets=2)
    with pytest.raises(QuotaExceededError):
        reg.charge("t", 1)            # dataset budget now exhausted
    snap = reg.snapshot()
    assert snap["t"]["rejects"] == 2 and snap["t"]["quota_bytes"] == 100


def test_typed_error_wire_roundtrip():
    for exc, cls in ((QuotaExceededError("over", tenant="t"),
                      QuotaExceededError),
                     (AuthError("who"), AuthError),
                     (RuntimeError("boom"), RuntimeError)):
        back = error_from_reply(error_reply(exc))
        assert type(back) is cls


# ---------------------------------------------------------------------------
# stats merge
# ---------------------------------------------------------------------------


def test_transfer_stats_merge_semantics():
    assert TransferStats.merge([]).nbytes == 0
    one = TransferStats("rdma_staged", nbytes=10, n_datasets=1,
                        to_staging_s=1.0, end_to_end_s=2.0,
                        write_wait_s=0.5, peak_inflight_bytes=7,
                        channels=[{"id": 0}])
    m1 = TransferStats.merge([one])
    assert (m1.nbytes, m1.engine) == (10, "rdma_staged")
    two = TransferStats("rdma_staged", nbytes=30, n_datasets=2,
                        to_staging_s=0.5, end_to_end_s=3.0,
                        write_wait_s=0.25, peak_inflight_bytes=5,
                        channels=[{"id": 1}], gateway={"epoch": "e"})
    m = TransferStats.merge([one, two])
    assert m.nbytes == 40 and m.n_datasets == 3        # flows sum
    assert m.write_wait_s == 0.75
    assert m.to_staging_s == 1.0 and m.end_to_end_s == 3.0   # walls max
    assert m.peak_inflight_bytes == 7                  # high-water max
    assert [c["id"] for c in m.channels] == [0, 1]
    assert m.gateway == {"epoch": "e"}
    other = TransferStats("scp_mem", nbytes=1)
    assert TransferStats.merge([one, other]).engine == "merged"


def test_analysis_stats_merge_semantics():
    assert AnalysisStats.merge([]).n_queries == 0
    a = AnalysisStats(endpoint="x", n_queries=2, query_s=1.0,
                      result_bytes=10, by_kind={"select": 2})
    b = AnalysisStats(endpoint="y", n_queries=3, n_retries=1,
                      query_s=0.5, result_bytes=5,
                      by_kind={"select": 1, "aggregate": 2})
    m = AnalysisStats.merge([a, b])
    assert m.endpoint == "x+y"
    assert m.n_queries == 5 and m.n_retries == 1      # everything sums
    assert m.query_s == 1.5 and m.result_bytes == 15
    assert m.by_kind == {"select": 3, "aggregate": 2}
    assert m.mean_query_s == pytest.approx(0.3)


def test_merge_histograms():
    h1 = {"counts": [1, 2], "edges": [0, 1, 2], "total": 3}
    h2 = {"counts": [3, 4], "edges": [0, 1, 2], "total": 7}
    m = merge_histograms([h1, h2])
    assert m == {"counts": [4, 6], "edges": [0, 1, 2], "total": 10}
    with pytest.raises(ValueError):
        merge_histograms([h1, {"counts": [1], "edges": [0, 9], "total": 1}])


# ---------------------------------------------------------------------------
# staging stop() hardening under health probes
# ---------------------------------------------------------------------------


def test_staging_stop_joins_cleanly_under_probes():
    sv = SavimeServer().start()
    st_srv = StagingServer(sv.addr, mem_capacity=1 << 20).start()
    stop_probing = threading.Event()

    def probe_loop():
        while not stop_probing.is_set():
            try:
                s = wire.connect(st_srv.addr, timeout=1.0)
                wire.request(s, {"op": "ping"})
                wire.request(s, {"op": "stats"})
                s.close()
            except OSError:
                return            # server went down mid-probe: expected

    probers = [threading.Thread(target=probe_loop, daemon=True)
               for _ in range(4)]
    for t in probers:
        t.start()
    time.sleep(0.15)              # let probes overlap the accept loop
    # probe-only connections must not count as data connections
    s = wire.connect(st_srv.addr)
    h, _ = wire.request(s, {"op": "stats"})
    assert h["conns"] == 0
    assert h["free_fraction"] == 1.0 and h["mem_capacity"] == 1 << 20
    wire.request(s, {"op": "hello"})      # first real op: now counted
    h, _ = wire.request(s, {"op": "stats"})
    assert h["conns"] == 1
    s.close()
    st_srv.stop()
    stop_probing.set()
    for t in probers:
        t.join(2.0)
    assert not any(t.is_alive() for t in probers)
    assert st_srv.live_threads() == 0     # no half-open serve threads
    sv.stop()


# ---------------------------------------------------------------------------
# gateway units
# ---------------------------------------------------------------------------


def test_fleet_credits_follow_worst_backend():
    gw = GatewayServer(_nodes(3))         # never started: pure unit
    try:
        backends = list(gw.backends.values())
        assert gw._fleet_credits(8, 8) == 8
        backends[1].free_fraction = 0.25  # one pressured backend...
        assert gw.fleet_free_fraction() == 0.25
        assert gw._fleet_credits(8, 8) == 2   # ...caps the whole fleet
        assert gw._fleet_credits(8, 1) == 1   # backend grant still binds
        backends[1].free_fraction = 0.0
        assert gw._fleet_credits(8, 8) == 1   # never zero
        backends[1].alive = False             # dead backends don't cap
        assert gw.fleet_free_fraction() == 1.0
    finally:
        gw.stop()


def test_gateway_client_typed_rejections():
    with StagingPool(2, mem_capacity=1 << 20,
                     tenants=[Tenant("tiny", quota_bytes=100)]) as pool:
        cli = GatewayClient(pool.addr, tenant="tiny")
        try:
            cli.admit("d0", 60)
            with pytest.raises(QuotaExceededError):
                cli.admit("d1", 60)
            with pytest.raises(QuotaExceededError):
                cli.admit_batch([("d2", 30), ("d3", 30)])   # all-or-nothing
            assert cli.admit("d4", 40)      # budget still has exactly 40
        finally:
            cli.close()


def test_gateway_rejects_unknown_token():
    with StagingPool(1, mem_capacity=1 << 20, require_auth=True,
                     tenants=[Tenant("a", token="tok")]) as pool:
        with pytest.raises(AuthError):
            GatewayClient(pool.addr, tenant="wrong").admit("d", 1)
        cli = GatewayClient(pool.addr, tenant="tok")
        try:
            assert cli.admit("d", 1)
        finally:
            cli.close()


# ---------------------------------------------------------------------------
# end-to-end: the N=3 acceptance scenario
# ---------------------------------------------------------------------------


RNG = np.random.default_rng(7)


def _stage_all(sess, arrays):
    for name, arr in arrays.items():
        sess.write(name, arr)
    sess.sync()
    sess.drain()


def _load_all(sess, tar, arrays, width, first=0):
    for i, name in enumerate(arrays):
        sess.run_savime(f'load_subtar({tar}, {name}, '
                        f'"{width * (first + i)}", "{width}", v)')


def test_e2e_pool_matches_single_server_bit_for_bit():
    """Block, striped-bin1 and coalesced datasets land ring-correctly
    across N=3 backends, and every aggregate/select answered through the
    gateway is byte-identical to the same data on one server."""
    width = 300
    arrays = {f"par_s{i}": RNG.standard_normal(width) for i in range(9)}
    ddl = f'create_tar(par, "x:0:{width * 9 - 1}", "v:float64")'
    ops = ("sum", "mean", "std", "min", "max", "count")

    # -- N=1 reference --------------------------------------------------
    sv1 = SavimeServer().start()
    st1 = StagingServer(sv1.addr, mem_capacity=64 << 20).start()
    ref = {}
    with TransferSession("rdma_staged",
                         TransportConfig(staging_addr=st1.addr)) as sess:
        sess.run_savime(ddl)
        _stage_all(sess, arrays)
        _load_all(sess, "par", arrays, width)
        for op in ops:
            ref[op] = sess.run_savime(f'aggregate("par", "v", "{op}")')
        ref["select"] = np.asarray(sess.run_savime('select("par", "v")'))
    st1.stop()
    sv1.stop()

    # -- N=3 pool, a different ingest path per third of the data --------
    with StagingPool(3, mem_capacity=64 << 20) as pool:
        base = TransportConfig(gateway_addr=pool.addr, block_size=1 << 20)
        variants = [
            base,                                             # block path
            base.replace(n_channels=2, stripe_bytes=1 << 10,
                         wire_format="bin1"),                 # striped bin1
            base.replace(coalesce_bytes=1 << 20),             # coalesced
        ]
        names = list(arrays)
        sessions = []
        try:
            for v, chunk in zip(variants,
                                (names[0:3], names[3:6], names[6:9])):
                sess = TransferSession("rdma_staged", v).open()
                if not sessions:
                    sess.run_savime(ddl)   # DDL fans out via the gateway
                sessions.append(sess)
                _stage_all(sess, {n: arrays[n] for n in chunk})
            ctl = sessions[0]
            _load_all(ctl, "par", arrays, width)

            # ring-correct landing: per-backend staged byte totals must
            # equal what the placement ring predicts, dataset by dataset
            gc = GatewayClient(pool.addr)
            ring = gc.ring
            gc.close()
            predicted = {f"backend{i}": 0 for i in range(3)}
            for n, a in arrays.items():
                predicted[ring.place(n).name] += a.nbytes
            landed = {k: v["bytes_in"]
                      for k, v in pool.backend_stats().items()}
            assert landed == predicted
            assert all(v > 0 for v in landed.values())   # data did spread

            # scatter-gather answers: byte-identical to the single server
            for op in ops:
                got = ctl.run_savime(f'aggregate("par", "v", "{op}")')
                assert got == ref[op], (op, got, ref[op])
            got_sel = np.asarray(ctl.run_savime('select("par", "v")'))
            assert got_sel.tobytes() == ref["select"].tobytes()

            # accounting parity: gateway admissions == Σ backend ingress
            gw_stats = ctl.server_stats()
            assert gw_stats["totals"]["admitted_bytes"] == \
                sum(landed.values())
            assert gw_stats["totals"]["admitted_datasets"] == len(arrays)
            assert gw_stats["live_backends"] == 3
        finally:
            for sess in sessions:
                sess.close()
        assert sessions[0].stats.gateway["n_backends"] == 3


def test_e2e_quota_rejection_is_typed_and_isolated():
    """A tenant over quota gets QuotaExceededError on both the block and
    the striped ingest path, while another tenant's traffic proceeds."""
    with StagingPool(2, mem_capacity=32 << 20,
                     tenants=[Tenant("capped", quota_bytes=10 << 10),
                              Tenant("roomy")]) as pool:
        base = TransportConfig(gateway_addr=pool.addr, tenant="capped")
        capped = TransferSession("rdma_staged", base).open()
        try:
            capped.write("q_s0", np.ones(1 << 10)).wait(10)    # 8 KiB: fits
            fut = capped.write("q_big", np.ones(1 << 14))      # 128 KiB: no
            with pytest.raises(QuotaExceededError):
                fut.wait(10)
            # striped path rejects with the same typed error
            striped = TransferSession("rdma_staged", base.replace(
                n_channels=2, stripe_bytes=512)).open()
            try:
                with pytest.raises(QuotaExceededError):
                    striped.write("q_big2", np.ones(1 << 14)).wait(10)
            finally:
                striped.close()
            # the other tenant is unaffected
            with TransferSession("rdma_staged", base.replace(
                    tenant="roomy")) as roomy:
                roomy.write("r_s0", np.ones(1 << 14)).wait(10)
        finally:
            capped.close()
        snap = capped.stats.gateway["tenants"]
        assert snap["capped"]["rejects"] >= 2
        assert snap["capped"]["bytes"] == (1 << 10) * 8
        assert snap["roomy"]["bytes"] == (1 << 14) * 8


def test_e2e_backend_death_remaps_without_losing_acked_data():
    width = 200
    with StagingPool(3, mem_capacity=32 << 20,
                     health_interval=0.05) as pool:
        cfg = TransportConfig(gateway_addr=pool.addr)
        with TransferSession("rdma_staged", cfg) as sess:
            sess.run_savime(
                f'create_tar(fx, "x:0:{width * 8 - 1}", "v:float64")')
            first = {f"fx_s{i}": RNG.standard_normal(width)
                     for i in range(4)}
            _stage_all(sess, first)
            _load_all(sess, "fx", first, width)
            # hard-kill one staging backend (its SAVIME — already holding
            # its subtars — stays up); health probes must fail it out
            pool.kill_backend(0)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sess.server_stats()["live_backends"] == 2:
                    break
                time.sleep(0.05)
            gw = sess.server_stats()
            assert gw["live_backends"] == 2
            assert gw["remaps"] >= 1

            # every acked dataset is still queryable through the gateway
            got = sess.run_savime('aggregate("fx", "v", "sum")')
            assert got == float(np.sum(np.concatenate(
                list(first.values()))))

            # new writes remap onto the shrunken ring and land
            more = {f"fx_s{i}": RNG.standard_normal(width)
                    for i in range(4, 8)}
            _stage_all(sess, more)
            _load_all(sess, "fx", more, width, first=4)
            total = sess.run_savime('aggregate("fx", "v", "sum")')
            assert total == float(np.sum(np.concatenate(
                list(first.values()) + list(more.values()))))


def test_e2e_watch_multiplexes_backends():
    width = 64
    with StagingPool(2, mem_capacity=16 << 20) as pool:
        cfg = TransportConfig(gateway_addr=pool.addr)
        with TransferSession("rdma_staged", cfg) as sess:
            sess.run_savime(
                f'create_tar(w, "x:0:{width * 4 - 1}", "v:float64")')
            arrays = {f"w_s{i}": RNG.standard_normal(width)
                      for i in range(4)}
            _stage_all(sess, arrays)
            with RouterSession(gateway_addr=pool.addr) as rs:
                with rs.watch("w", timeout=5.0, max_events=4) as sub:
                    _load_all(sess, "w", arrays, width)
                    events = list(sub)
        assert len(events) == 4
        assert all(ev.tar == "w" for ev in events)
        assert {ev.origin[0] for ev in events} == \
            {width * i for i in range(4)}


def test_gateway_proxies_legacy_clients():
    """A client that knows nothing about gateways (``staging_addr``
    pointed at the gateway) still works on every ingest path: write_req
    / stripe / batch ops are proxied with placement and fleet-capped
    credits."""
    width = 256
    with StagingPool(2, mem_capacity=32 << 20) as pool:
        legacy = TransportConfig(staging_addr=pool.addr)  # NOT gateway_addr
        with TransferSession("rdma_staged", legacy) as sess:
            sess.run_savime(
                f'create_tar(lg, "x:0:{width * 12 - 1}", "v:float64")')
            arrays = {f"lg_s{i}": RNG.standard_normal(width)
                      for i in range(6)}
            _stage_all(sess, arrays)
            _load_all(sess, "lg", arrays, width)
            total = sess.run_savime('aggregate("lg", "v", "sum")')
            assert total == float(np.sum(np.concatenate(
                list(arrays.values()))))
        # striped legacy client (ctrl + stripe conns all hit the gateway)
        with TransferSession("rdma_staged", legacy.replace(
                n_channels=2, stripe_bytes=1 << 10)) as sess2:
            more = {f"lg_s{i}": RNG.standard_normal(width)
                    for i in range(6, 9)}
            _stage_all(sess2, more)
            _load_all(sess2, "lg", more, width, first=6)
            got = sess2.run_savime('aggregate("lg", "v", "count")')
            assert got == width * 9
        # coalesced legacy client (batch_open/batch_write scatter relay)
        with TransferSession("rdma_staged", legacy.replace(
                coalesce_bytes=1 << 20)) as sess3:
            batch = {f"lg_s{i}": RNG.standard_normal(width)
                     for i in range(9, 12)}
            _stage_all(sess3, batch)
        landed = pool.backend_stats()
        assert sum(v["bytes_in"] for v in landed.values()) == width * 8 * 12
        assert all(v["bytes_in"] > 0 for v in landed.values())
