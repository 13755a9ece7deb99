"""Field egress while an analyst queries: the paper's deployment.

Producer, closed loop: each step of the configuration's field is made on
the chip from the seed and handed to ``InTransitSink.stage_array``; after
every ``group_steps`` steps one ``flush`` makes the group queryable. Each
group is its own TAR; once a group is queryable, the group
``retain_groups`` back is dropped through the public ``DropTar``, so the
host holds a bounded number of steps however long the run.

Analyst, open loop at ``query_rate_hz``: selects the inclusive box
``query_lo``..``query_hi`` of the newest queryable step, timed from when
the query was due.

After the window: ``check_answers`` of the analyst's answers, drawn from
the seed, and the whole last step, queried back, are compared with the
configuration's reference field under the guarantee of the codec used.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from harness import host_rss, quantile, seed_key, seed_rng


class Analyst(threading.Thread):
    """Open-loop queries at a fixed rate against the newest group."""

    def __init__(self, ctx, addr, box, rate, t0, t_end, newest, keep):
        super().__init__(name="bench-analyst", daemon=True)
        self.ctx, self.addr, self.box = ctx, addr, box
        self.rate, self.t0, self.t_end = rate, t0, t_end
        self.newest = newest          # callable -> (tar, step index t)
        self.keep = keep              # callable(i) -> keep answer i?
        self.latency, self.late, self.answers = [], [], []
        self.failed = 0
        self.errors: list[str] = []

    def run(self):
        from repro import analysis
        lo, hi = self.box
        with analysis.AnalysisSession(self.addr) as an:
            i = 0
            while True:
                due = self.t0 + i / self.rate
                if due >= self.t_end:
                    return
                now = time.perf_counter()
                if now < due:
                    time.sleep(due - now)
                start = time.perf_counter()
                self.late.append(start - due)
                tar, t, j = self.newest()
                try:
                    with self.ctx.span("query"):
                        res = an.execute(analysis.tar(tar).attr("v").range(
                            (j, *lo), (j, *hi)).select())
                except Exception as e:  # noqa: BLE001 — a failed query counts
                    self.failed += 1
                    self.errors.append(repr(e))
                else:
                    self.latency.append(time.perf_counter() - due)
                    self.ctx.query_elapsed.append(res.elapsed_s)
                    if self.keep(i):
                        self.answers.append((t, res.array))
                i += 1


def run(ctx) -> dict:
    jax = ctx.jax
    from repro import analysis
    from repro.analysis.query import DropTar
    from repro.core import (InTransitConfig, InTransitSink, SavimeServer,
                            StagingServer)

    cfg, tr = ctx.config, ctx.traffic
    ref = ctx.reference_module()
    shape = tuple(cfg["mesh"])
    G, retain = tr["group_steps"], tr["retain_groups"]
    codec = tr["codec"]
    span = ctx.span
    key = seed_key(jax, ctx.seed)
    step_bytes = int(np.prod(shape)) * 4
    ctx.query_elapsed = []

    savime = SavimeServer().start()
    staging = StagingServer(savime.addr,
                            mem_capacity=cfg["staging_mem_bytes"]).start()
    sink = InTransitSink(staging.addr, InTransitConfig(
        tar_prefix="field", transport=cfg["transport"],
        block_size=cfg["block_size"], io_threads=cfg["io_threads"],
        codec=codec))
    ctx.mark("servers")
    groups: dict[int, str] = {}          # queryable groups -> TAR name
    state = {"newest": None}
    lock = threading.Lock()
    stale: list[float] = []
    analyst = None

    def newest():
        with lock:
            return state["newest"]

    def produce_group(g: int, in_window: bool, steps: int = G) -> float:
        """Stage the group's steps and flush; returns the flush's end."""
        name = f"v{g}"
        starts = []
        for j in range(steps):
            t = g * G + j
            with span("field_step"):
                x = ref.field(key, t, cfg)
                x.block_until_ready()
            starts.append(time.perf_counter())
            with span("stage_array"):
                sink.stage_array(name, x, step=j)
            del x
        with span("flush"):
            sink.flush()
        t_done = time.perf_counter()
        if in_window:
            stale.extend(t_done - s for s in starts)
        tar = f"field_{name}"
        with lock:
            groups[g] = tar
            state["newest"] = (tar, g * G + steps - 1, steps - 1)
        old = g - retain
        if old in groups:
            with lock:
                del groups[old]
            sink.session.run_savime(DropTar(f"field_v{old}"))
        return t_done

    try:
        # set-up: compile the producer, stage and flush one step, and warm
        # the analyst's connection and select on it
        produce_group(0, in_window=False, steps=1)
        ctx.mark("first_step")
        lo, hi = tuple(tr["query_lo"]), tuple(tr["query_hi"])
        with analysis.AnalysisSession(savime.addr) as an:
            tar_, _, j = newest()
            an.execute(analysis.tar(tar_).attr("v").range(
                (j, *lo), (j, *hi)).select())
        ctx.setup_done()
        rss0 = host_rss()

        rng = seed_rng(ctx.seed, 1)
        n_due = int(ctx.seconds * tr["query_rate_hz"])
        kept = set(rng.choice(n_due, size=min(tr["check_answers"], n_due),
                              replace=False).tolist()) if n_due else set()
        codec0 = dict(sink.session.stats.codec)
        with ctx.window():
            t0 = ctx.window_t0
            t_end = t0 + ctx.seconds
            analyst = Analyst(ctx, savime.addr, (lo, hi),
                              tr["query_rate_hz"], t0, t_end, newest,
                              kept.__contains__)
            analyst.start()
            g = 1
            while time.perf_counter() < t_end:
                t_last = produce_group(g, in_window=True)
                g += 1
            analyst.join(timeout=120)
        window_s = t_last - t0
        n_groups = g - 1
        rss1 = host_rss()
        disk_fallbacks = staging.stats["disk_fallbacks"]
        ctx.read_memory()
        if analyst.is_alive():
            raise RuntimeError("the analyst did not stop within 120 s")

        # the last step, queried whole
        tar_, t_last, j = newest()
        with analysis.AnalysisSession(savime.addr) as an:
            whole = an.execute(analysis.tar(tar_).attr("v").range(
                (j, 0, 0, 0), (j, *(n - 1 for n in shape))).select())
        answers = analyst.answers + [(t_last, whole.array)]
        boxes = [(lo, hi)] * len(analyst.answers) + \
            [((0, 0, 0), tuple(n - 1 for n in shape))]
        codec1 = dict(sink.session.stats.codec)
        codec_stats = {k: codec1[k] - codec0.get(k, 0) for k in
                       ("encode_s", "datasets", "raw_bytes", "wire_bytes")
                       if k in codec1}
    finally:
        if analyst is not None and analyst.is_alive():
            analyst.join(timeout=120)
        sink.close()
        staging.stop()
        savime.stop()

    # the reference, after the program's state is gone
    t = time.perf_counter()
    number = compare(ref, key, cfg, codec, answers, boxes)
    check_s = time.perf_counter() - t
    name = "mismatched_values" if codec == "none" else "int8_err_over_bound"
    checks = {name: {"value": number, "limit": tr["limits"][name]},
              "failed_queries": {"value": analyst.failed,
                                 "limit": tr["limits"]["failed_queries"]}}

    lat_ms = np.asarray(analyst.latency) * 1e3
    late_ms = np.asarray(analyst.late) * 1e3
    notes = [
        f"{n_groups} groups of {G} steps ({n_groups * G * step_bytes} B) "
        f"queryable in {window_s:.3f} s",
        f"analyst: {len(analyst.latency)} answers, {analyst.failed} failed "
        f"{analyst.errors[:3]}; started late by p50 "
        f"{np.percentile(late_ms, 50):.3f} ms, max {late_ms.max():.3f} ms",
        f"staleness samples {len(stale)}, query samples {lat_ms.size}",
        f"reference: {len(answers)} answers compared in {check_s:.3f} s",
        f"host RSS {rss0} B after set-up, {rss1} B at the window's end; "
        f"staging fell back to disk {disk_fallbacks} times",
    ]
    return {
        "end_to_end": {
            "ingest_MBps": n_groups * G * step_bytes / 1e6 / window_s,
            "staleness_p90_ms": quantile(stale, 0.90) * 1e3,
            "query_p95_ms": quantile(lat_ms, 0.95),
        },
        "attempted": len(analyst.late) + n_groups * G,
        "failed": analyst.failed,
        "checks": checks, "notes": notes,
        "window_s": window_s, "steps": n_groups * G, "groups": n_groups,
        "query_elapsed_s": list(ctx.query_elapsed),
        "codec": codec_stats,
        "spans": {k: span.durations(k, t0) for k in span.times},
    }


def compare(ref, key, cfg, codec, answers, boxes) -> float:
    """The number the cell is judged by, over every compared answer:
    mismatched float32 values (codec none), or the largest error over the
    int8-block bound (codec int8-block)."""
    shape = tuple(cfg["mesh"])
    worst = 0.0
    by_step: dict[int, list] = {}
    for (t, arr), box in zip(answers, boxes):
        by_step.setdefault(t, []).append((arr, box))
    for t, items in sorted(by_step.items()):
        want = np.asarray(ref.field(key, t, cfg))
        amax = ref.block_amax(want) if codec != "none" else None
        for arr, (lo, hi) in items:
            sub = want[tuple(slice(a, b + 1) for a, b in zip(lo, hi))]
            if codec == "none":
                worst += ref.mismatched(arr, sub)
            else:
                flat = ref.flat_index(shape, lo, hi)
                worst = max(worst, ref.err_over_bound(arr, sub, flat, amax))
    return float(worst)
