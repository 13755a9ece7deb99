"""The benchmark's general part: find a cell by name, run its driver, reduce
what it recorded to metrics, and print the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

    BENCHMARK.json                     cells, metrics, bounds
    bench/configs/<config>.json        sizes, source, assumptions, guarantees
    bench/configs/<config>_ref.py      the plain reference of that config
    bench/traffic/<traffic>.json       driver name, traffic parameters, limits
    bench/drivers/<driver>.py          the traffic loop: run(ctx) -> dict
    bench/metrics/<metric>.py          read(run) -> float | None

A driver calls ``ctx.setup_done()`` once everything it will use is warm,
wraps the measured window in ``ctx.window()``, calls ``ctx.read_memory()``
once the window has closed and before its reference runs, and times the
calls it makes into the program with ``ctx.span(name)``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"          # compile cache, traces, TPU logs (ignored)


class BenchError(RuntimeError):
    """The cell cannot be run here (no chip, unknown name, bad file)."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return json.loads(path.read_text())


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (names here may hold '.' and '-')."""
    if not path.is_file():
        raise BenchError(f"{path} not found")
    mod_name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def deep_merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def resolve_cell(name: str, bench: dict, overrides: Optional[dict] = None
                 ) -> dict:
    """The cell's entry with its configuration and traffic files loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    config = json.loads((ROOT / c["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    overrides = overrides or {}
    return {"workload": w, "config": deep_merge(config, overrides.get("config")),
            "config_name": c["name"],
            "traffic": deep_merge(traffic, overrides.get("traffic"))}


def cell_metrics(name: str, bench: dict, kind: str) -> list[dict]:
    """The end-to-end or per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


# ---------------------------------------------------------------------------
# run context handed to a driver
# ---------------------------------------------------------------------------


class Spans:
    """Host-clock spans around calls into the program. Each span is also a
    profiler annotation, so a traced run can attribute device idle time to
    what the host was doing."""

    def __init__(self, annotate: Optional[Callable] = None):
        self.times: dict[str, list[tuple[float, float]]] = {}
        self._annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = self._annotate(name) if self._annotate else \
            contextlib.nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.times.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list[float]:
        """Durations of the spans of this name that start in [lo, hi)."""
        return [b - a for a, b in self.times.get(name, []) if lo <= a < hi]


class Context:
    def __init__(self, *, cell: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, jax_mod=None, log: Callable = print):
        self.name = cell["workload"]["name"]
        self.config = cell["config"]
        self.config_name = cell["config_name"]
        self.traffic = cell["traffic"]
        self.chips = int(cell["workload"].get("chips", 1))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.jax = jax_mod
        self.log = log
        annotate = None
        if jax_mod is not None:
            annotate = jax_mod.profiler.TraceAnnotation
        self.span = Spans(annotate)
        self.setup_s: Optional[float] = None
        self.window_t0: Optional[float] = None
        self.window_t1: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir: Optional[Path] = None
        self.marks: list[tuple[str, float]] = []

    def reference_module(self):
        return load_module(BENCH / "configs" / f"{self.config_name}_ref.py")

    def mark(self, name: str) -> None:
        """Note the end of one phase of set-up, for the set-up split."""
        self.marks.append((name, time.perf_counter() - self.t_start))

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        return self.setup_s

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced when the run is a traced one."""
        if self.setup_s is None:
            self.setup_done()
        tracing = self.trace and self.jax is not None
        if tracing:
            self.trace_dir = CACHE / "trace" / self.name
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans only, no call tracing
            opts.host_tracer_level = 2
            self.jax.profiler.start_trace(str(self.trace_dir),
                                          profiler_options=opts)
        try:
            ann = (self.jax.profiler.TraceAnnotation("bench_window")
                   if self.jax is not None else contextlib.nullcontext())
            with ann:
                self.window_t0 = time.perf_counter()
                yield self
                self.window_t1 = time.perf_counter()
        finally:
            if tracing:
                self.jax.profiler.stop_trace()

    def read_memory(self) -> Optional[int]:
        """Peak device memory on the fullest chip, read once the window
        has closed (before any reference runs on the chip)."""
        if self.jax is None:
            return None
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.jax.local_devices()[:self.chips]]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes


# ---------------------------------------------------------------------------
# small statistics shared by drivers and readers
# ---------------------------------------------------------------------------


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) with linear interpolation between ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def host_rss() -> int:
    """Resident bytes of this process now (not the peak)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def seed_key(jax, seed: int):
    """A PRNG key from a seed of up to 64 bits (larger seeds wrap)."""
    import numpy as np
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def seed_rng(seed: int, *stream: int):
    """A numpy generator for one stream of the seed's host-side draws."""
    import numpy as np
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def setup_paths() -> None:
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def setup_jax_env() -> None:
    """Fix the compile cache inside the checkout before JAX is imported,
    so the program's own cache helper takes this directory too."""
    cache = CACHE / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    setup_paths()


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             compile_cache: bool = True, t_start: Optional[float] = None,
             log: Callable = print) -> dict:
    """Run one cell; returns the result object (without printing it).
    Tests run cells on the CPU with ``require_tpu=False``, ``overrides``
    of sizes and ``compile_cache=False``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark()
    cell = resolve_cell(workload, bench, overrides)
    if compile_cache:
        setup_jax_env()
    else:
        setup_paths()
    import jax
    if compile_cache:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_info(jax, int(cell["workload"].get("chips", 1)),
                         require_tpu)
    log(f"[bench] {workload}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"device {device}")

    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=t_start, jax_mod=jax, log=log)
    ctx.mark("jax_init")
    driver = load_module(BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    rec = driver.run(ctx)
    if ctx.memory_peak_bytes is None:
        ctx.read_memory()

    checks = rec["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics: dict[str, dict] = {}
    result: dict[str, Any] = {"correct": correct,
                              "attempted": int(rec["attempted"]),
                              "failed": int(rec["failed"])}
    if not trace:
        values = dict(rec["end_to_end"], setup_s=ctx.setup_s)
        for m in cell_metrics(workload, bench, "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        tr = load_module(BENCH / "trace.py", "bench_trace")
        t = time.perf_counter()
        red = tr.reduce_trace(ctx.trace_dir, ctx.chips) if ctx.trace_dir \
            else None
        if red is not None:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            log(f"[bench] trace: {red['trace_file_bytes']} B reduced in "
                f"{time.perf_counter() - t:.3f} s; {red['n_gaps']} idle "
                f"gaps, longest {red['longest_gap_s']!r} s; programs "
                f"{red['modules']}")
        run = {"record": rec, "trace": red, "config": ctx.config,
               "traffic": ctx.traffic, "device": device}
        for m in cell_metrics(workload, bench, "per_layer"):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    else:
        result["device"] = device
    result["checks"] = checks
    for line in rec.get("notes", []):
        log(f"[bench] {line}")
    t_prev, split = 0.0, []
    for name, t in ctx.marks + [("rest", ctx.setup_s)]:
        split.append(f"{name} {t - t_prev:.3f} s")
        t_prev = t
    log(f"[bench] setup split: {', '.join(split)}")
    log(f"[bench] setup_s {ctx.setup_s}, host max RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} B, "
        f"memory_peak_bytes {ctx.memory_peak_bytes}")
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"[bench] cannot run: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    for k, c in result["checks"].items():
        print(f"[check] {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
