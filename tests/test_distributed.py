"""Multi-device distribution tests — run in subprocesses with 8 fake CPU
devices (XLA_FLAGS must be set before jax init, and the main pytest process
must keep its single-device view)."""
import os
import subprocess
import sys

_HERE = os.path.dirname(__file__)


def _run(check: str, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_HERE, "..", "src")
    r = subprocess.run(
        [sys.executable, os.path.join(_HERE, "distributed_checks.py"), check],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"{check}:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}"
    assert f"{check} OK" in r.stdout


def test_sharded_train_step_matches_single_device():
    _run("check_sharded_equals_single")


def test_compressed_cross_pod_gradient_reduce():
    _run("check_compressed_pod_reduce")


def test_compressed_reduce_at_nondivisible_block_rows():
    _run("check_compressed_reduce_nondivisible")


def test_checkpoint_reshard_across_meshes():
    _run("check_reshard_restore")


def test_sequence_sharded_decode_matches_replicated():
    _run("check_seq_sharded_decode")
