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

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM, device_put_batch
from repro.launch import egress
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
    queryable and before the servers stop (``savime_addr`` is None behind
    ``--pool``)."""
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
    ap.add_argument("--compress-pods", action="store_true")
    ap.add_argument("--egress", default="diag",
                    choices=["none", "diag", "grads_int8"])
    egress.add_args(ap, "per-step diagnostics")
    args = egress.parse_args(ap, argv)

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

    with egress.open_egress(args, "train") as eg:
        sink = eg.sink if eg else None
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
                before_close(state, eg.savime_addr)
    return summary


if __name__ == "__main__":
    main()
