"""Each driver runs a whole cell at a small size on the CPU, and the
command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402
import harness  # noqa: E402

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_small_on_the_cpu(workload):
    res = _cells.run(workload, seconds=1.0)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(workload, BENCH,
                                                    "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_only_layer_metrics(workload):
    res = _cells.run(workload, seconds=1.0, trace=True)
    assert res["correct"] is True, res["checks"]
    layer = {m["name"] for m in harness.cell_metrics(workload, BENCH,
                                                     "per_layer")}
    assert set(res["metrics"]) <= layer
    # on the CPU only host-side layers read; device metrics stay silent
    assert not any(k.startswith("device.") or k.endswith("_roofline")
                   or "mfu" in k for k in res["metrics"])
    assert res["metrics"], "no layer metric read at all"
    assert res["device"]["window_s"] > 0
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_gives_the_same_inputs():
    import jax
    import numpy as np
    field = harness.load_module(
        harness.BENCH / "configs" / "seismic-hpc4e-201x501x501_ref.py")
    serve = harness.load_module(
        harness.BENCH / "configs" / "musicgen-medium-serve_ref.py")
    cfg = harness.deep_merge(json.loads(
        (harness.BENCH / "configs" / "seismic-hpc4e-201x501x501.json")
        .read_text()), _cells.FIELD["config"])
    m = _cells.SERVE["config"]["model"]
    m = dict(json.loads((harness.BENCH / "configs"
                         / "musicgen-medium-serve.json").read_text())["model"],
             **m)

    def inputs(seed):
        key = harness.seed_key(jax, seed)
        w = serve.make_weights(key, m)
        return ([np.asarray(field.field(key, t, cfg)) for t in (0, 5)]
                + [np.asarray(w[k]) for k in sorted(w)]
                + [harness.seed_rng(seed, 1, b).integers(0, 256, (2, 8))
                   for b in (0, 3)])

    a, b, c = inputs(_cells.SEED), inputs(_cells.SEED), inputs(_cells.SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(x, z) for x, z in zip(a, c))


def test_field_steps_follow_the_float64_formula():
    import jax
    import numpy as np
    field = harness.load_module(
        harness.BENCH / "configs" / "seismic-hpc4e-201x501x501_ref.py")
    cfg = harness.deep_merge(json.loads(
        (harness.BENCH / "configs" / "seismic-hpc4e-201x501x501.json")
        .read_text()), _cells.FIELD["config"])
    key = harness.seed_key(jax, _cells.SEED)
    for t in (0, 1, 7):
        got = np.asarray(field.field(key, t, cfg))
        want = field.field_np(key, t, cfg)
        assert got.dtype == np.float32 and got.shape == tuple(cfg["mesh"])
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
        assert np.abs(want).max() > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_run_exits_nonzero_without_a_tpu():
    p = _command(harness.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
