"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Prefill a batch of prompts, then decode greedily with a donated KV cache —
the production path the decode_* dry-run shapes lower. Optionally stages
per-request latency diagnostics in transit (SAVIME) like a real fleet
would.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import analysis, transport
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import Model
from repro.runtime import enable_compile_cache
from repro.train import ServeSetup


def build_mesh(spec: str):
    if spec == "single":
        return make_production_mesh()
    if spec == "multi":
        return make_production_mesh(multi_pod=True)
    parts = [int(x) for x in spec.split("x")]
    return make_debug_mesh(*parts)


def main(argv=None) -> dict:
    """Serve one batch; returns a summary of what was generated, timed and
    (with --intransit --analyzer) queried back from SAVIME."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--intransit", action="store_true",
                    help="stage per-step latencies into SAVIME")
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
                         "pages stored once (needs --page-kb)")
    ap.add_argument("--codec", default="none",
                    help="egress reduction codec for staged datasets "
                         "(none | delta-rle | int8-block; DESIGN.md §13)")
    ap.add_argument("--decode-at", default="staging",
                    choices=["staging", "query"],
                    help="decode coded datasets at ingest (default) or "
                         "store them compressed and decode lazily on the "
                         "staging->SAVIME hop")
    ap.add_argument("--analyzer", default=None,
                    choices=analysis.analyzers.available(),
                    help="summarize staged decode latencies with a "
                         "registered analyzer (needs --intransit)")
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
    args = ap.parse_args(argv)
    if args.analyzer and not args.intransit:
        ap.error("--analyzer requires --intransit")
    if (args.tenant or args.quota_mb) and not args.pool:
        ap.error("--tenant/--quota-mb require --pool")
    if args.pool and args.transport != "rdma_staged":
        ap.error("--pool requires the rdma_staged transport")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    mesh = build_mesh(args.mesh)
    B, S, N = args.batch, args.prompt_len, args.new_tokens
    setup = ServeSetup(model, mesh, global_batch=B)
    print(f"[serve] {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}, batch {B} x prompt {S} + {N} new")

    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab_size)
    prefill = jax.jit(setup.prefill_fn(max_len=S + N))
    decode = jax.jit(setup.decode_fn(), donate_argnums=(1,))

    sink = staging = savime = pool = fault_sched = None
    tenant_token = None
    if args.intransit:
        from repro.core import (InTransitConfig, InTransitSink, SavimeServer,
                                StagingServer)
        if args.pool:
            from repro.gateway import StagingPool, Tenant
            tenants = ()
            quota = (args.quota_mb << 20) or None
            if args.tenant:
                name, _, token = args.tenant.partition(":")
                tenant_token = token or name
                tenants = (Tenant(name, token=token or None,
                                  quota_bytes=quota),)
            pool = StagingPool(args.pool,
                               tenants=tenants,
                               default_quota_bytes=None if args.tenant
                               else quota,
                               staging_kwargs={
                                   "page_bytes": args.page_kb << 10,
                                   "spill_dir": args.spill_dir,
                                   "dedup": args.dedup}).start()
            sink_addr = pool.addr
            print(f"[serve] staging pool: {args.pool} backends behind "
                  f"gateway {pool.addr}")
        else:
            savime = SavimeServer().start()
            staging = StagingServer(savime.addr,
                                    page_bytes=args.page_kb << 10,
                                    spill_dir=args.spill_dir,
                                    dedup=args.dedup).start()
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
            print(f"[serve] fault plan armed (seed={plan.seed}, "
                  f"{len(plan.rules)} rule(s))")
        sink = InTransitSink(sink_addr,
                             InTransitConfig(tar_prefix="serve",
                                             transport=args.transport,
                                             n_channels=args.channels,
                                             wire_format=args.wire_format,
                                             coalesce_bytes=(
                                                 args.coalesce_kb << 10),
                                             page_bytes=args.page_kb << 10,
                                             spill_dir=args.spill_dir,
                                             dedup=args.dedup,
                                             gateway=bool(args.pool),
                                             tenant=tenant_token,
                                             codec=args.codec,
                                             decode_at=args.decode_at))

    key = jax.random.PRNGKey(2)
    with jax.set_mesh(mesh):
        # compile both steps before the timed run
        t0 = time.perf_counter()
        _, warm = prefill(params, {"tokens": prompts})
        jax.block_until_ready(decode(params, warm, {
            "tokens": prompts[:, :1], "pos": jnp.full((B,), S, jnp.int32)}))
        t_compile = time.perf_counter() - t0

        t0 = time.perf_counter()
        logits, cache = prefill(params, {"tokens": prompts})
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        def sample(lg, key):
            if args.temperature <= 0:
                return jnp.argmax(lg, -1)[:, None]
            return jax.random.categorical(
                key, lg / args.temperature, -1)[:, None]

        tok = sample(logits, key)
        out, lat = [tok], []
        for i in range(N - 1):
            key, sub = jax.random.split(key)
            pos = jnp.full((B,), S + i, jnp.int32)
            t1 = time.perf_counter()
            logits, cache = decode(params, cache, {"tokens": tok, "pos": pos})
            tok = sample(logits, sub)
            jax.block_until_ready(tok)
            lat.append(time.perf_counter() - t1)
            out.append(tok)
            if sink is not None:
                sink.stage_array("decode_ms",
                                 np.float32([lat[-1] * 1e3]), step=i)

    gen = jnp.concatenate(out, axis=1)
    lat_ms = np.asarray(lat) * 1e3
    print(f"[serve] compile {t_compile:.1f} s; "
          f"prefill {t_prefill * 1e3:.0f} ms; decode p50 "
          f"{np.percentile(lat_ms, 50):.1f} ms/tok, p99 "
          f"{np.percentile(lat_ms, 99):.1f} ms/tok "
          f"({B * 1e3 / np.mean(lat_ms):.1f} tok/s aggregate)")
    print(f"[serve] sample (req 0): {gen[0, :16].tolist()}")
    summary = {"prompts": np.asarray(prompts), "tokens": np.asarray(gen),
               "last_logits": np.asarray(logits), "compile_s": t_compile,
               "prefill_ms": t_prefill * 1e3,
               "decode_ms": lat_ms}
    if sink is not None:
        sink.flush()
        if args.analyzer:
            if pool is not None:
                from repro.gateway import RouterSession
                an_ctx = RouterSession(gateway_addr=pool.addr)
            else:
                an_ctx = analysis.AnalysisSession(savime.addr)
            with an_ctx as an:
                res = an.execute(
                    analysis.tar("serve_decode_ms").attr("v").select())
                a = analysis.analyzers.create(args.analyzer)
                a.update(res)
                s = a.summary()
                print(f"[serve] analyzer[{s.analyzer}] over "
                      f"{res.shape} staged latencies: {s.payload}")
                summary["staged_decode_ms"] = res.array
                summary["analyzer"] = s.payload
        summary["staged_bytes"] = sink.staged_bytes
        sink.close()
        if fault_sched is not None:
            from repro.faults import uninstall
            fault_sched.stop()
            uninstall()
        if pool is not None:
            gw = sink.session.stats.gateway
            if gw:
                print(f"[serve] gateway: {gw['totals']} across "
                      f"{gw['live_backends']}/{gw['n_backends']} backends; "
                      f"tenants: {gw['tenants']}")
            pool.stop()
        else:
            staging.stop()
            savime.stop()
    return summary


if __name__ == "__main__":
    main()
