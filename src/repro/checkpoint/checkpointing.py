"""Checkpointing — asynchronous, riding the paper's staging path.

Backends:
  dir      — .npy shards + manifest.json in a directory (restore side).
  staging  — checkpoint shards ride the in-transit sink's TransferSession
             (any registered transport; rdma_staged by default): the write
             is asynchronous (paper's producer never blocks), lands in
             tmpfs, is forwarded to SAVIME by the FCFS pool, and is
             queryable as TARS arrays (a checkpoint you can *analyze* in
             place). A dir copy is kept for restore.

Restore is mesh-shape agnostic: leaves are device_put against the target
mesh's shardings (elastic restart: 512 -> 256 chips just works).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Optional

import jax
import numpy as np

from repro.core.intransit import InTransitSink
from repro.core.queues import FCFSPool

PyTree = Any


def _flatten(tree: PyTree) -> dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = leaf
    return out


class CheckpointManager:
    def __init__(self, directory: str, sink: Optional[InTransitSink] = None,
                 keep: int = 3, async_writes: bool = True):
        self.dir = directory
        self.sink = sink
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._pool = FCFSPool(2, "ckpt-io") if async_writes else None
        self._writes: list = []   # handles of async writes not yet waited
        self._lock = threading.Lock()

    # -- save ---------------------------------------------------------------
    def save(self, state: PyTree, step: int) -> str:
        """Non-blocking (async_writes): device->host copy happens here, file
        and staging I/O on background threads."""
        flat = _flatten(state)
        host = {k: np.asarray(v) for k, v in flat.items()}  # device_get
        cdir = os.path.join(self.dir, f"step_{step:08d}")
        os.makedirs(cdir, exist_ok=True)
        manifest = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                    for k, v in host.items()}
        with open(os.path.join(cdir, "manifest.json"), "w") as f:
            json.dump({"step": step, "leaves": manifest}, f)

        def write_all():
            for k, v in host.items():
                np.save(os.path.join(cdir, k.replace("/", "__") + ".npy"), v)
            with open(os.path.join(cdir, "COMMITTED"), "w") as f:
                f.write("ok")
            self._gc()

        if self._pool:
            self._writes.append(
                self._pool.submit(write_all, name=f"ckpt-{step}"))
        else:
            write_all()
        if self.sink is not None:  # analyzable checkpoint via SAVIME
            for k, v in host.items():
                if v.ndim >= 1 and v.size > 0:
                    self.sink.stage_array("ckpt_" + k.replace("/", "_"),
                                          v, step=step)
        return cdir

    def wait(self) -> None:
        """Block until every save so far is written; raise if any failed."""
        if self._pool:
            self._pool.sync()
            writes, self._writes = self._writes, []
            failed = [h for h in writes if h.error is not None]
            if failed:
                raise RuntimeError(
                    f"{len(failed)} checkpoint write(s) failed, first "
                    f"{failed[0].name}: {failed[0].error}") from failed[0].error
        if self.sink:
            self.sink.flush()

    # -- restore --------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        steps = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(self.dir, d, "COMMITTED")):
                steps.append(int(d.split("_")[1]))
        return max(steps) if steps else None

    def restore(self, abstract_state: PyTree, step: Optional[int] = None,
                shardings: Optional[PyTree] = None) -> PyTree:
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {self.dir}")
        cdir = os.path.join(self.dir, f"step_{step:08d}")
        flat_abs = _flatten(abstract_state)
        flat_sh = _flatten(shardings) if shardings is not None else None
        out = {}
        for k, spec in flat_abs.items():
            arr = np.load(os.path.join(cdir, k.replace("/", "__") + ".npy"))
            arr = arr.astype(spec.dtype).reshape(spec.shape)
            if flat_sh is not None:
                out[k] = jax.device_put(arr, flat_sh[k])  # reshard-on-restore
            else:
                out[k] = jax.numpy.asarray(arr)
        return _unflatten_like(abstract_state, out)

    def _gc(self) -> None:
        with self._lock:
            steps = sorted(
                int(d.split("_")[1]) for d in os.listdir(self.dir)
                if d.startswith("step_") and os.path.exists(
                    os.path.join(self.dir, d, "COMMITTED")))
            for s in steps[:-self.keep]:
                import shutil
                shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                              ignore_errors=True)


def _unflatten_like(tree: PyTree, flat: dict[str, Any]) -> PyTree:
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for path, _ in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        leaves.append(flat[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)
