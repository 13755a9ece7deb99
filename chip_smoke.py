#!/usr/bin/env python3
"""Run the in-transit producer path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips (a 2x2 mesh)

One chip: serve full-width musicgen-medium through ``repro.launch.serve``
with its decode latencies staged into SAVIME and queried back; check the
cached decode against a full-sequence prefill. Then put two steps of the
paper's 201x501x501 float32 velocity field on the chip, stage them through
``InTransitSink`` into SAVIME, query them back bit for bit, and run the
Pallas int8-block encode on the chip against its XLA twin.

Four chips: train full-width musicgen-medium through ``repro.launch.train``
on a 2x2 (data x model) mesh with checkpoints staged into SAVIME; check the
step-1 loss against the same step on a 4x1 mesh and every staged leaf of the
last checkpoint against the state on the chip.

Timings and sizes go to earlier lines. The last line is one JSON object
naming the device. Without a TPU, or when any check fails, the script exits
nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "musicgen-medium"
FIELD = dict(nx=201, ny=501, nz=501)   # arXiv:1805.06425 §4 velocity mesh


class SmokeFailure(AssertionError):
    pass


def need(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_summary(jax, n_used: int) -> dict:
    devs = jax.devices()
    need(devs[0].platform == "tpu",
         f"JAX found no TPU (platform {devs[0].platform!r})")
    need(len(devs) >= n_used, f"need {n_used} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def serve_phase(jax, np) -> None:
    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.mesh import make_debug_mesh
    from repro.models import Model
    from repro.train import ServeSetup

    B, S, N = 4, 64, 32
    s = serve.main(["--arch", ARCH, "--mesh", "1x1", "--intransit",
                    "--analyzer", "running_stats", "--batch", str(B),
                    "--prompt-len", str(S), "--new-tokens", str(N)])
    cfg = get_config(ARCH)
    tok = s["tokens"]
    need(tok.shape == (B, N), f"tokens shape {tok.shape}")
    need(bool(((tok >= 0) & (tok < cfg.vocab_size)).all()),
         "generated tokens outside the vocabulary")
    need(bool(np.isfinite(s["last_logits"]).all()), "non-finite logits")
    staged = np.asarray(s["staged_decode_ms"], np.float32).reshape(-1)
    need(staged.size == N - 1,
         f"{staged.size} staged decode latencies, expected {N - 1}")
    need(np.array_equal(staged, s["decode_ms"].astype(np.float32)),
         "staged decode latencies differ from the measured ones")
    need(s["analyzer"]["count"] == N - 1,
         f"analyzer counted {s['analyzer']['count']}, expected {N - 1}")

    # reference: the same greedy sequence through one full prefill must
    # give the logits the cached decode produced at its last step
    model = Model(cfg)
    mesh = make_debug_mesh(1, 1)
    setup = ServeSetup(model, mesh, global_batch=B)
    params = model.init(jax.random.PRNGKey(0))       # the launcher's seed
    seq = np.concatenate([s["prompts"], tok[:, :-1]], axis=1)
    with jax.set_mesh(mesh):
        ref, _ = jax.jit(setup.prefill_fn(max_len=S + N))(
            params, {"tokens": seq})
    ref = np.asarray(ref, np.float32)
    del params
    got = s["last_logits"].astype(np.float32)
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    log(f"serve: compile {s['compile_s']:.3f} s, prefill "
        f"{s['prefill_ms']:.3f} ms, decode p50 "
        f"{np.percentile(s['decode_ms'], 50):.3f} ms p99 "
        f"{np.percentile(s['decode_ms'], 99):.3f} ms, "
        f"{staged.size} latencies ({s['staged_bytes']} B) staged and "
        f"queried back, decode-vs-prefill logits "
        f"max rel diff {rel:.6f}")
    need(rel <= 0.05, f"cached decode logits differ from a full prefill "
                     f"by {rel:.4f} of max |logit|")


def field_phase(jax, np) -> None:
    from repro import analysis
    from repro.codec.int8block import Int8BlockCodec
    from repro.core import (InTransitConfig, InTransitSink, SavimeServer,
                            StagingServer)
    from repro.data.seismic import SeismicConfig, SeismicField

    t0 = time.perf_counter()
    field = SeismicField(SeismicConfig(**FIELD))
    host = [field.step(t).astype(np.float32) for t in (0, 1)]
    dev = [jax.device_put(h) for h in host]
    jax.block_until_ready(dev)
    log(f"field: 2 steps of {host[0].shape} float32 "
        f"({host[0].nbytes / 1e6:.1f} MB each) made and put on the chip in "
        f"{time.perf_counter() - t0:.3f} s")

    savime = SavimeServer().start()
    staging = StagingServer(savime.addr).start()
    sink = InTransitSink(staging.addr, InTransitConfig(tar_prefix="paper"))
    try:
        t0 = time.perf_counter()
        for t, d in enumerate(dev):
            sink.stage_array("velocity", d, step=t)
        t_stage = time.perf_counter() - t0
        sink.flush()
        t_flush = time.perf_counter() - t0
        log(f"field: staged {sink.staged_bytes / 1e6:.1f} MB; stage_array "
            f"{t_stage * 1e3:.3f} ms, queryable after {t_flush * 1e3:.3f} ms")
        nx, ny, nz = host[0].shape
        lo = (nx // 4, ny // 5, nz // 2)             # inclusive sub-range
        hi = (nx // 2, ny // 2 + ny // 10, nz // 2 + nz // 8)
        with analysis.AnalysisSession(savime.addr) as an:
            for t, d in enumerate(dev):
                want = np.asarray(jax.device_get(d))
                whole = an.execute(analysis.tar("paper_velocity").attr("v")
                                   .range((t, 0, 0, 0),
                                          (t, nx - 1, ny - 1, nz - 1))
                                   .select())
                need(whole.array.dtype == np.float32
                     and np.array_equal(whole.array.reshape(want.shape)
                                        .view(np.uint32),
                                        want.view(np.uint32)),
                     f"step {t}: queried field differs from the device's")
                part = an.execute(analysis.tar("paper_velocity").attr("v")
                                  .range((t, *lo), (t, *hi)).select())
                sub = want[lo[0]:hi[0] + 1, lo[1]:hi[1] + 1, lo[2]:hi[2] + 1]
                need(np.array_equal(part.array.reshape(sub.shape)
                                    .view(np.uint32), sub.view(np.uint32)),
                     f"step {t}: queried sub-range differs")
                log(f"field: step {t} queried back bit-identical "
                    f"(whole {whole.elapsed_s * 1e3:.3f} ms, sub-range "
                    f"{part.array.size} values {part.elapsed_s * 1e3:.3f} ms)")
    finally:
        sink.close()
        staging.stop()
        savime.stop()

    # the device-side int8 encode: Pallas kernel vs its XLA twin
    x, xh = dev[1], host[1]
    pallas, xla = Int8BlockCodec(impl="pallas"), Int8BlockCodec(impl="xla")
    for c in (pallas, xla):                           # compile
        c.encode(x)
    t0 = time.perf_counter()
    pay_p, meta_p = pallas.encode(x)
    t_p = time.perf_counter() - t0
    t0 = time.perf_counter()
    pay_x, meta_x = xla.encode(x)
    t_x = time.perf_counter() - t0
    nb = -(-xh.size // meta_p["block"])
    sp = np.frombuffer(pay_p[:nb * 4], np.float32)
    sx = np.frombuffer(pay_x[:nb * 4], np.float32)
    qp = np.frombuffer(pay_p[nb * 4:], np.int8)
    qx = np.frombuffer(pay_x[nb * 4:], np.int8)
    log(f"int8-block: {len(pay_p) / 1e6:.1f} MB from {xh.nbytes / 1e6:.1f} "
        f"MB; encode pallas {t_p * 1e3:.3f} ms, xla {t_x * 1e3:.3f} ms "
        f"(device work + device->host copy); scales differing "
        f"{int((sp != sx).sum())}/{nb}, values differing "
        f"{int((qp != qx).sum())}/{qp.size}")
    need(meta_p == meta_x and pay_p == pay_x,
         "Pallas int8-block encode differs from the XLA encode")
    dq = pallas.decode(pay_p, meta_p).view(np.float32)
    err = np.abs(xh.reshape(-1) - dq)
    scale = np.repeat(sp, meta_p["block"])[:xh.size]
    ratio = float((err / scale).max())
    log(f"int8-block: max |x - dq| / scale = {ratio:.6f}")
    need(ratio <= 0.5 + 2 ** -14, f"|x - dq| exceeds scale/2 ({ratio})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(jax, np) -> None:
    from repro.checkpoint.checkpointing import _flatten
    from repro import analysis
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM, device_put_batch
    from repro.launch import train
    from repro.launch.mesh import make_debug_mesh
    from repro.models import Model
    from repro.train import TrainConfig, TrainSetup

    steps, batch, seq, lr = 4, 8, 128, 3e-3
    cfg = get_config(ARCH)

    # reference: step 1 on a 4x1 (pure data-parallel) mesh, with the
    # launcher's seed, schedule and first batch
    mesh = make_debug_mesh(4, 1)
    setup = TrainSetup(Model(cfg), mesh, TrainConfig(
        peak_lr=lr, warmup_steps=min(20, steps // 5 + 1), total_steps=steps))
    # the launcher keeps a full .npy copy of each of its two checkpoints
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(setup.abstract_state()))
    free = shutil.disk_usage(tempfile.gettempdir()).free
    log(f"train: state {state_bytes / 1e9:.3f} GB; {free / 1e9:.3f} GB "
        f"free in {tempfile.gettempdir()}")
    need(free > 2 * state_bytes + (2 << 30),
         f"two checkpoints of {state_bytes / 1e9:.1f} GB do not fit the "
         f"{free / 1e9:.1f} GB free in {tempfile.gettempdir()}")
    state = setup.init_state(jax.random.PRNGKey(0))
    b = next(SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        n_prefix=cfg.n_prefix, d_model=cfg.d_model)).batches())
    with jax.set_mesh(mesh):
        _, m, _ = setup.jitted()(state, device_put_batch(b, mesh,
                                                        setup.rules))
        ref_loss = float(m["loss"])
    del state, setup
    log(f"train: 4x1 reference step-1 loss {ref_loss:.6f}")

    checked = {}

    def check_staged(state, savime_addr):
        last = int(jax.device_get(state["step"]))
        t0 = time.perf_counter()
        with analysis.AnalysisSession(savime_addr) as an:
            for k, leaf in _flatten(state).items():
                if leaf.ndim == 0:
                    continue
                want = np.asarray(jax.device_get(leaf))
                name = "run_ckpt_" + k.replace("/", "_")
                got = an.execute(analysis.tar(name).attr("v").range(
                    (last,) + (0,) * want.ndim,
                    (last,) + tuple(n - 1 for n in want.shape)).select())
                need(got.array.dtype == want.dtype and np.array_equal(
                    got.array.reshape(want.shape), want),
                    f"staged checkpoint leaf {k} differs from the state")
                checked[k] = want.nbytes
        log(f"train: {len(checked)} staged leaves of step {last} "
            f"({sum(checked.values()) / 1e6:.1f} MB) queried back equal to "
            f"the state in {time.perf_counter() - t0:.3f} s")

    with tempfile.TemporaryDirectory(prefix="smoke-ckpt-") as ckpt_dir:
        s = train.main(["--arch", ARCH, "--mesh", "2x2", "--intransit",
                        "--steps", str(steps), "--ckpt-every", "2",
                        "--batch", str(batch), "--seq", str(seq),
                        "--lr", str(lr), "--ckpt-dir", ckpt_dir],
                       before_close=check_staged)
    need(s["restarts"] == 0, f"supervisor restarted {s['restarts']} times")
    need(len(s["losses"]) == steps and np.isfinite(s["losses"]).all(),
         f"losses {s['losses']}")
    need(checked, "no staged checkpoint leaf was checked")
    rel = abs(s["losses"][0] - ref_loss) / abs(ref_loss)
    log(f"train: 2x2 losses {s['losses']}; step-1 rel diff vs 4x1 "
        f"{rel:.6f}; {s['seconds']:.3f} s for {steps} steps; staged "
        f"{s['staged_bytes'] / 1e6:.1f} MB")
    need(rel <= 1e-2, f"2x2 step-1 loss {s['losses'][0]} vs 4x1 {ref_loss}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training path on four chips")
    args = ap.parse_args()

    import jax
    import numpy as np
    device = device_summary(jax, 4 if args.four_chips else 1)
    log(f"device: {device}")

    from repro.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase(jax, np)
    else:
        serve_phase(jax, np)
        field_phase(jax, np)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
