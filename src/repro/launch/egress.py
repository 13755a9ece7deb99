"""Egress flags and set-up shared by the launchers.

``add_args`` declares the in-transit flags, ``parse_args`` checks how they
combine, and ``open_egress`` starts SAVIME and staging (or a gateway pool),
arms a fault plan, builds the :class:`~repro.core.InTransitSink` and takes
all of it down again.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
from typing import Iterator, Optional

from repro import analysis, transport
from repro.core import (InTransitConfig, InTransitSink, SavimeServer,
                        StagingServer)


def add_args(ap: argparse.ArgumentParser, staged: str) -> None:
    """Add the egress flags; ``staged`` says what ``--intransit`` stages."""
    ap.add_argument("--intransit", action="store_true",
                    help=f"stage {staged} into SAVIME")
    ap.add_argument("--transport", default="rdma_staged",
                    choices=transport.available(),
                    help="egress engine for the in-transit sink")
    ap.add_argument("--channels", type=int, default=1,
                    help="stripe egress across N concurrent connections "
                         "with credit-based flow control (1 = off)")
    ap.add_argument("--wire-format", default="json",
                    choices=["json", "bin1"],
                    help="negotiate the struct-packed binary fast path "
                         "for hot data frames (falls back to json)")
    ap.add_argument("--coalesce-kb", type=int, default=0,
                    help="coalesce datasets below this size into jumbo "
                         "batched frames (KiB, 0 = off)")
    ap.add_argument("--page-kb", type=int, default=0,
                    help="run staging on the paged store with this page "
                         "size (KiB, 0 = flat regions); cold pages spill "
                         "to disk under memory pressure (DESIGN.md §11)")
    ap.add_argument("--spill-dir", default=None,
                    help="directory for spilled cold pages (default: a "
                         "spill/ subdir of the staging disk tier)")
    ap.add_argument("--dedup", action="store_true",
                    help="content-addressed page dedup: identical sealed "
                         "pages (e.g. repeated checkpoint shards) stored "
                         "once (needs --page-kb)")
    ap.add_argument("--codec", default="none",
                    help="egress reduction codec for staged datasets "
                         "(none | delta-rle | int8-block; DESIGN.md §13)")
    ap.add_argument("--decode-at", default="staging",
                    choices=["staging", "query"],
                    help="decode coded datasets at ingest (default) or "
                         "store them compressed and decode lazily on the "
                         "staging->SAVIME hop")
    ap.add_argument("--pool", type=int, default=0,
                    help="run N staging backends behind one gateway "
                         "(DESIGN.md §12; 0 = single staging server)")
    ap.add_argument("--tenant", default=None, metavar="NAME[:TOKEN]",
                    help="gateway tenant to write as (needs --pool); "
                         "NAME:TOKEN registers the tenant with that token")
    ap.add_argument("--quota-mb", type=int, default=0,
                    help="per-tenant byte quota in MiB (needs --pool; "
                         "0 = unlimited)")
    ap.add_argument("--faults", default=None,
                    help="seeded fault plan for the staging path — a DSL "
                         "string ('seed=42;drop:op=stripe,prob=0.01;"
                         "kill:target=staging:0,at_s=0.5') or a JSON plan "
                         "file; exercises retry/replay (DESIGN.md §15)")


def parse_args(ap: argparse.ArgumentParser, argv) -> argparse.Namespace:
    """Parse ``argv`` and reject egress flags that do not combine."""
    args = ap.parse_args(argv)
    if (args.tenant or args.quota_mb) and not args.pool:
        ap.error("--tenant/--quota-mb require --pool")
    if args.pool and args.transport != "rdma_staged":
        ap.error("--pool requires the rdma_staged transport")
    return args


@dataclasses.dataclass
class Egress:
    """A running egress path: the sink and where its data can be queried."""

    sink: InTransitSink
    savime_addr: Optional[str]    # the one SAVIME; None behind a pool
    gateway_addr: Optional[str]   # the pool's gateway; None without one

    def analysis(self):
        """A query session over everything staged."""
        if self.gateway_addr is not None:
            from repro.gateway import RouterSession
            return RouterSession(gateway_addr=self.gateway_addr)
        return analysis.AnalysisSession(self.savime_addr)


@contextlib.contextmanager
def open_egress(args: argparse.Namespace, tag: str,
                tar_prefix: str = "run") -> Iterator[Optional[Egress]]:
    """Run the egress path the flags describe; yields None without
    ``--intransit``. ``tag`` prefixes the log lines. On a clean exit the
    sink is flushed and closed; in any case the fault plan is disarmed
    and the servers stop."""
    if not args.intransit:
        yield None
        return
    staging_kw = {"page_bytes": args.page_kb << 10,
                  "spill_dir": args.spill_dir, "dedup": args.dedup}
    pool = staging = savime = fault_sched = None
    tenant_token = None
    try:
        if args.pool:
            from repro.gateway import StagingPool, Tenant
            tenants = ()
            quota = (args.quota_mb << 20) or None
            if args.tenant:
                name, _, token = args.tenant.partition(":")
                tenant_token = token or name
                tenants = (Tenant(name, token=token or None,
                                  quota_bytes=quota),)
            pool = StagingPool(args.pool, tenants=tenants,
                               default_quota_bytes=None if args.tenant
                               else quota,
                               staging_kwargs=staging_kw).start()
            sink_addr = pool.addr
            print(f"[{tag}] staging pool: {args.pool} backends behind "
                  f"gateway {pool.addr}")
        else:
            savime = SavimeServer().start()
            staging = StagingServer(savime.addr, **staging_kw).start()
            # the staged path attaches to staging; copy-emulation
            # transports (scp_*, ssh_direct) reach SAVIME directly, as
            # the baselines do
            sink_addr = (staging.addr if args.transport == "rdma_staged"
                         else savime.addr)
        if args.faults:
            from repro.faults import FaultPlan, FaultScheduler, install
            plan = FaultPlan.parse(args.faults)
            if pool is not None:
                scope = [pool.addr] + [st.addr for st in pool.stagings] \
                    + [sv.addr for sv in pool.savimes]
                targets = {"gateway": pool.gateway.stop}
                for i, st in enumerate(pool.stagings):
                    targets[f"staging:{i}"] = st.stop
                for i, sv in enumerate(pool.savimes):
                    targets[f"savime:{i}"] = sv.stop
            else:
                scope = [staging.addr, savime.addr]
                targets = {"staging:0": staging.stop,
                           "savime:0": savime.stop}
            install(plan, scope=scope)
            fault_sched = FaultScheduler(plan, targets).start()
            print(f"[{tag}] fault plan armed (seed={plan.seed}, "
                  f"{len(plan.rules)} rule(s))")
        sink = InTransitSink(sink_addr, InTransitConfig(
            tar_prefix=tar_prefix, transport=args.transport,
            n_channels=args.channels, wire_format=args.wire_format,
            coalesce_bytes=args.coalesce_kb << 10,
            page_bytes=args.page_kb << 10, spill_dir=args.spill_dir,
            dedup=args.dedup, gateway=bool(args.pool), tenant=tenant_token,
            codec=args.codec, decode_at=args.decode_at))
        print(f"[{tag}] in-transit sink --{args.transport}"
              f"(x{args.channels} channels, {args.wire_format} wire"
              f"{', coalescing' if args.coalesce_kb else ''}"
              f"{f', codec={args.codec}' if args.codec != 'none' else ''})"
              f"--> {sink_addr}")
        yield Egress(sink, savime.addr if savime else None,
                     pool.addr if pool else None)
        sink.close()
        gw = sink.session.stats.gateway
        if gw:
            print(f"[{tag}] gateway: {gw['totals']} across "
                  f"{gw['live_backends']}/{gw['n_backends']} backends; "
                  f"tenants: {gw['tenants']}")
    finally:
        if fault_sched is not None:
            from repro.faults import uninstall
            fault_sched.stop()
            uninstall()
        for server in (pool, staging, savime):
            if server is not None:
                server.stop()
