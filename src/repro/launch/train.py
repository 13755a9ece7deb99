"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires together: config registry -> model -> mesh -> TrainSetup (pjit,
ZeRO-1, optional compressed cross-pod grads) -> synthetic data pipeline ->
fault-tolerant Supervisor (async checkpoints through the staging path) ->
in-transit diagnostics sink (the paper's consumer is a live SAVIME you can
query while training).
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro import transport
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import InTransitConfig, InTransitSink, SavimeServer, StagingServer
from repro.data import DataConfig, SyntheticLM, device_put_batch
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import Model
from repro.runtime import Supervisor, SupervisorConfig, enable_compile_cache
from repro.train import TrainConfig, TrainSetup


def build_mesh(spec: str):
    if spec == "single":
        return make_production_mesh()
    if spec == "multi":
        return make_production_mesh(multi_pod=True)
    parts = [int(x) for x in spec.split("x")]
    if len(parts) == 2:
        return make_debug_mesh(*parts)
    return make_debug_mesh(parts[1], parts[2], pod=parts[0])


def main(argv=None, before_close=None) -> dict:
    """Train; returns a summary of the run. With --intransit,
    ``before_close(state, savime_addr)`` runs once everything staged is
    queryable and before the servers stop."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--mesh", default="1x1",
                    help="single | multi | DxM | PxDxM (debug sizes)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "repro-ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--intransit", action="store_true",
                    help="stage per-step diagnostics into SAVIME")
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
    ap.add_argument("--compress-pods", action="store_true")
    ap.add_argument("--egress", default="diag",
                    choices=["none", "diag", "grads_int8"])
    ap.add_argument("--faults", default=None,
                    help="seeded fault plan for the staging path — a DSL "
                         "string ('seed=42;drop:op=stripe,prob=0.01;"
                         "kill:target=staging:0,at_s=0.5') or a JSON plan "
                         "file; exercises retry/replay (DESIGN.md §15)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    mesh = build_mesh(args.mesh)
    print(f"[train] {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")

    setup = TrainSetup(model, mesh, TrainConfig(
        peak_lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
        total_steps=args.steps, compress_pods=args.compress_pods,
        egress=args.egress))
    state = setup.init_state(jax.random.PRNGKey(0))

    sink = savime = staging = None
    fault_sched = None
    if args.intransit:
        savime = SavimeServer().start()
        staging = StagingServer(savime.addr,
                                page_bytes=args.page_kb << 10,
                                spill_dir=args.spill_dir,
                                dedup=args.dedup).start()
        if args.faults:
            from repro.faults import FaultPlan, FaultScheduler, install
            plan = FaultPlan.parse(args.faults)
            install(plan, scope=[staging.addr, savime.addr])
            fault_sched = FaultScheduler(plan, {
                "staging:0": staging.stop,
                "savime:0": savime.stop}).start()
            print(f"[train] fault plan armed (seed={plan.seed}, "
                  f"{len(plan.rules)} rule(s))")
        # the staged path attaches to staging; copy-emulation transports
        # (scp_*, ssh_direct) reach SAVIME directly, as the baselines do
        sink_addr = (staging.addr if args.transport == "rdma_staged"
                     else savime.addr)
        sink = InTransitSink(sink_addr, InTransitConfig(
            io_threads=2, transport=args.transport,
            n_channels=args.channels, wire_format=args.wire_format,
            coalesce_bytes=args.coalesce_kb << 10,
            page_bytes=args.page_kb << 10, spill_dir=args.spill_dir,
            dedup=args.dedup,
            codec=args.codec, decode_at=args.decode_at))
        print(f"[train] in-transit sink --{args.transport}"
              f"(x{args.channels} channels, {args.wire_format} wire"
              f"{', coalescing' if args.coalesce_kb else ''}"
              f"{f', codec={args.codec}' if args.codec != 'none' else ''})"
              f"--> SAVIME {savime.addr}")

    ckpt = CheckpointManager(args.ckpt_dir, sink=sink)
    sup = Supervisor(setup.jitted(), ckpt,
                     SupervisorConfig(ckpt_every=args.ckpt_every))

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, n_prefix=cfg.n_prefix,
                    d_model=cfg.d_model)
    raw = SyntheticLM(dc).batches()

    def batches():
        for b in raw:
            yield device_put_batch(b, mesh, setup.rules)

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        state = sup.run(state, batches(), args.steps,
                        abstract_state=setup.abstract_state(),
                        shardings=setup.state_shardings())
    dt = time.perf_counter() - t0
    losses = [m["loss"] for m in sup.metrics_log if "loss" in m]
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({dt / max(args.steps, 1) * 1e3:.0f} ms/step) "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    summary = {"losses": losses, "restarts": sup.restarts, "seconds": dt}
    if sink is not None:
        sink.flush()
        print(f"[train] staged {sink.staged_arrays} arrays, "
              f"{sink.staged_bytes / 1e6:.1f} MB into SAVIME")
        summary["staged_bytes"] = sink.staged_bytes
        if before_close is not None:
            before_close(state, savime.addr)
        sink.close()
        if fault_sched is not None:
            from repro.faults import uninstall
            fault_sched.stop()
            uninstall()
        staging.stop()
        savime.stop()
    return summary


if __name__ == "__main__":
    main()
