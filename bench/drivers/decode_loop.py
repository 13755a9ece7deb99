"""Closed-loop serving: batches of greedy requests through the program's
prefill and cached decode, one batch in flight, as ``launch/serve.py``
serves one; optionally with per-step telemetry staged into SAVIME.

Traffic parameters: ``batch`` requests of ``prompt_len`` prompt tokens and
``new_tokens`` generated tokens each, back to back. With ``telemetry`` every
decode step stages its latency (4 B) and the logits row of one request of
the batch, drawn from the seed (vocab x 4 B), and each batch ends with one
flush. Prompts are drawn from the seed; every seed gets the same sizes.

After the window: the staged telemetry is queried back and compared with
what the loop staged, and ``ref_requests`` finished requests, drawn from
the seed, are run through the configuration's plain reference, which
judges each served token by how far its logit lies below the best, and
the logits the program served by their distance from its own.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import seed_key, seed_rng

#: what each weight of the reference is called at the end of the
#: program's parameter paths
PROGRAM_NAMES = {("embed", "table"): "table", ("embed", "head"): "head",
                 ("final_ln",): "final_ln", ("ln1",): "ln1", ("ln2",): "ln2",
                 ("attn", "wq"): "wq", ("attn", "wk"): "wk",
                 ("attn", "wv"): "wv", ("attn", "wo"): "wo",
                 ("mlp", "wi"): "wi", ("mlp", "wo"): "wf"}


def to_program_params(jax, weights: dict, abstract) -> dict:
    """Arrange the benchmark's weights in the program's parameter tree,
    checking every leaf's shape and dtype against the program's own."""
    def pick(path, leaf):
        keys = tuple(getattr(p, "key", p) for p in path)
        for suffix, name in PROGRAM_NAMES.items():
            if keys[-len(suffix):] == suffix:
                w = weights[name]
                if w.shape != leaf.shape or w.dtype != leaf.dtype:
                    raise ValueError(f"{'/'.join(map(str, keys))}: program "
                                     f"wants {leaf.shape} {leaf.dtype}, "
                                     f"weights are {w.shape} {w.dtype}")
                return w
        raise ValueError(f"no weight for program leaf {keys}")
    return jax.tree_util.tree_map_with_path(pick, abstract)


def program_config(ctx):
    from repro.configs import get_config
    base = get_config(ctx.config["arch"])
    names = {f.name for f in dataclasses.fields(base)}
    return dataclasses.replace(base, **{k: v for k, v in
                                        ctx.config["model"].items()
                                        if k in names})


def run(ctx) -> dict:
    jax = ctx.jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_debug_mesh
    from repro.models import Model
    from repro.train import ServeSetup

    tr, m = ctx.traffic, ctx.config["model"]
    B, S, N = tr["batch"], tr["prompt_len"], tr["new_tokens"]
    telemetry = bool(tr.get("telemetry"))
    span = ctx.span
    ref = ctx.reference_module()

    model = Model(program_config(ctx))
    mesh = make_debug_mesh(1, 1)
    setup = ServeSetup(model, mesh, global_batch=B)
    key = seed_key(jax, ctx.seed)
    weights = jax.block_until_ready(ref.make_weights(key, m))
    ctx.mark("weights")
    params = to_program_params(jax, weights, jax.eval_shape(model.init, key))
    prefill = jax.jit(setup.prefill_fn(max_len=S + N))
    decode = jax.jit(setup.decode_fn(), donate_argnums=(1,))
    greedy = jax.jit(lambda lg: jnp.argmax(lg, -1)[:, None].astype(jnp.int32))
    pick = jax.jit(lambda lg, r: lg[r].astype(jnp.float32))

    def prompts_of(b, stream=1):
        return seed_rng(ctx.seed, stream, b).integers(
            0, m["vocab_size"], (B, S), dtype=np.int32)

    def row_of(b):
        return int(seed_rng(ctx.seed, 2, b).integers(B))

    sink = staging = savime = None
    if telemetry:
        from repro.core import (InTransitConfig, InTransitSink, SavimeServer,
                                StagingServer)
        savime = SavimeServer().start()
        staging = StagingServer(savime.addr).start()
        sink = InTransitSink(staging.addr, InTransitConfig(tar_prefix="serve"))
    staged_ms: list[np.float32] = []
    staged_rows: list = []

    def stage(step, lat_s, logits, r):
        with span("stage_array"):
            v = np.float32(lat_s * 1e3)
            sink.stage_array("decode_ms", np.asarray([v]), step=step)
            row = pick(logits, r)
            sink.stage_array("logits", row, step=step)
        staged_ms.append(v)
        staged_rows.append(row)

    try:
        with jax.set_mesh(mesh):
            # warm every program the window runs: prefill, decode (twice,
            # so a donated cache is taken back), argmax, the row pick and
            # the sink's first writes, flush and DDL
            logits, cache = prefill(params, {"tokens": prompts_of(0, stream=4)})
            tok = greedy(logits)
            tok.block_until_ready()
            ctx.mark("prefill_warm")
            for i in range(2):
                logits, cache = decode(params, cache, {
                    "tokens": tok, "pos": jnp.full((B,), S + i, jnp.int32)})
                tok = greedy(logits)
                tok.block_until_ready()
                if sink is not None:
                    stage(len(staged_ms), 0.0, logits, 0)
            ctx.mark("decode_warm")
            if sink is not None:
                sink.flush()
            del logits, cache
            ctx.setup_done()

            served, batches, n_tokens = {}, 0, 0
            lat, positions = [], []
            with ctx.window():
                t0 = ctx.window_t0
                t_end = t0 + ctx.seconds
                while time.perf_counter() < t_end:
                    b = batches
                    r = row_of(b)
                    with span("prefill"):
                        logits, cache = prefill(
                            params, {"tokens": prompts_of(b)})
                    with span("sample"):
                        tok = greedy(logits)
                        tok.block_until_ready()
                    toks, outs = [tok], [logits]
                    n_tokens += B
                    for i in range(N - 1):
                        if time.perf_counter() >= t_end:
                            break
                        t1 = time.perf_counter()
                        with span("decode_step"):
                            logits, cache = decode(params, cache, {
                                "tokens": tok,
                                "pos": jnp.full((B,), S + i, jnp.int32)})
                        with span("sample"):
                            tok = greedy(logits)
                            tok.block_until_ready()
                        lat.append(time.perf_counter() - t1)
                        positions.append(S + i)
                        toks.append(tok)
                        outs.append(logits)
                        n_tokens += B
                        if sink is not None:
                            stage(len(staged_ms), lat[-1], logits, r)
                    if len(toks) == N:
                        served[b] = (toks, outs)
                    logits = cache = None     # this batch's cache is done
                    if sink is not None:
                        with span("flush"):
                            sink.flush()
                    batches += 1
            window_s = ctx.window_t1 - t0
            ctx.read_memory()
            served = {b: (np.concatenate([np.asarray(t) for t in toks], 1),
                          outs) for b, (toks, outs) in served.items()}

        checks, notes = {}, []
        if sink is not None:
            checks["telemetry_mismatch"] = {
                "value": telemetry_mismatch(savime.addr, staged_ms,
                                            staged_rows),
                "limit": tr["limits"]["telemetry_mismatch"]}
    finally:
        if sink is not None:
            sink.close()
            staging.stop()
            savime.stop()

    # the reference, over a sample of finished requests
    n_finished = len(served)
    finished = [(b, i) for b in sorted(served) for i in range(B)]
    idx = seed_rng(ctx.seed, 3).choice(
        len(finished), size=min(tr["ref_requests"], len(finished)),
        replace=False)
    picks = [finished[j] for j in sorted(idx)]
    if picks:
        pr = np.stack([prompts_of(b)[i] for b, i in picks])
        sv = np.stack([served[b][0][i] for b, i in picks])
        lg = np.stack([np.stack([np.asarray(o[i], np.float32)
                                 for o in served[b][1]]) for b, i in picks])
        del served
        t = time.perf_counter()
        ref_logits = ref.served_logits(weights, pr, sv, m)
        got = ref.judge(ref_logits, sv, lg)
        notes.append(f"reference: {len(picks)} requests judged in "
                     f"{time.perf_counter() - t:.3f} s: {got}")
    else:
        got = {"logit_gap": float("inf"), "logit_err": float("inf")}
        notes.append("reference: no request finished in the window")
    for k in ("logit_err", "logit_gap"):
        checks[k] = {"value": got[k], "limit": tr["limits"][k]}
    lat_ms = np.asarray(lat) * 1e3
    notes.append(f"served {batches} batches ({n_finished} finished), "
                 f"{n_tokens} tokens in {window_s:.3f} s; decode step p50 "
                 f"{np.percentile(lat_ms, 50):.3f} ms p95 "
                 f"{np.percentile(lat_ms, 95):.3f} ms over {lat_ms.size}")
    return {
        "end_to_end": {"decode_tok_s": n_tokens / window_s},
        "attempted": batches * B, "failed": 0,
        "checks": checks, "notes": notes,
        "window_s": window_s, "tokens": n_tokens, "batch": B,
        "prompt_len": S, "prefills": batches, "decode_positions": positions,
        "step_latency_s": lat,
        "spans": {k: span.durations(k, t0) for k in span.times},
    }


def telemetry_mismatch(savime_addr, staged_ms, staged_rows) -> int:
    """Staged values that did not come back from SAVIME as staged: every
    latency and every logits row, by step."""
    from repro import analysis
    want_ms = np.asarray(staged_ms, np.float32)
    want_rows = np.stack([np.asarray(r, np.float32) for r in staged_rows])
    n = want_ms.size
    with analysis.AnalysisSession(savime_addr) as an:
        got_ms = an.execute(analysis.tar("serve_decode_ms").attr("v").range(
            (0, 0), (n - 1, 0)).select()).array.reshape(-1)
        got_rows = an.execute(analysis.tar("serve_logits").attr("v").range(
            (0, 0), (n - 1, want_rows.shape[1] - 1)).select()).array
    bad = int((got_ms.view(np.uint32) != want_ms.view(np.uint32)).sum())
    bad += int((got_rows.reshape(want_rows.shape).view(np.uint32)
                != want_rows.view(np.uint32)).sum())
    return bad
