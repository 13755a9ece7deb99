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

from repro import analysis
from repro.configs import get_config
from repro.launch import egress
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
    ap.add_argument("--analyzer", default=None,
                    choices=analysis.analyzers.available(),
                    help="summarize staged decode latencies with a "
                         "registered analyzer (needs --intransit)")
    egress.add_args(ap, "per-step latencies")
    args = egress.parse_args(ap, argv)
    if args.analyzer and not args.intransit:
        ap.error("--analyzer requires --intransit")

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

    with egress.open_egress(args, "serve", tar_prefix="serve") as eg:
        sink = eg.sink if eg else None
        key = jax.random.PRNGKey(2)
        with jax.set_mesh(mesh):
            # compile both steps before the timed run
            t0 = time.perf_counter()
            _, warm = prefill(params, {"tokens": prompts})
            jax.block_until_ready(decode(params, warm, {
                "tokens": prompts[:, :1],
                "pos": jnp.full((B,), S, jnp.int32)}))
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
                logits, cache = decode(params, cache,
                                       {"tokens": tok, "pos": pos})
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
                with eg.analysis() as an:
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
    return summary


if __name__ == "__main__":
    main()
