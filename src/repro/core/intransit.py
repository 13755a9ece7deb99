"""In-transit analysis sink for JAX jobs — the paper's technique as a
first-class training/serving feature.

The training/serving loop produces *quantities of interest* (simulation
fields, diagnostics tensors, activation samples, checkpoint shards). The
sink ships them through the full paper pipeline without blocking the step:

    device arrays --(device_get)--> host --libstaging(async, RDMA-emulated,
    block knob)--> staging tmpfs --(sendfile, FCFS pool)--> SAVIME TARS

DDL is automatic: each staged array gets a TAR whose dimensions mirror its
shape (+ a leading `step` dimension), and a ``load_subtar`` is issued once
the dataset lands in SAVIME — so analytical clients can query any range of
any step while the job keeps running (the paper's §6 goal).

Data reduction (paper §6 future work) is the transport's egress codec
(``codec=``, DESIGN.md §13): ``int8-block`` quantizes each dataset on the
client and staging decodes it, so the TAR keeps the array's own dtype.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional

import numpy as np

from repro import obs
from repro.analysis.query import CreateTar, LoadSubtar
from repro.core.tars import Attribute, Dimension
from repro.transport import TransferSession, TransportConfig

MAX_STEPS = 1_000_000  # upper bound of the `step` dimension in DDL


@dataclasses.dataclass(frozen=True)
class InTransitConfig(TransportConfig):
    """The sink's configuration: every :class:`TransportConfig` knob, plus
    what only the sink owns. The sink's ``addr`` sets the addresses."""

    block_size: int = 16 << 20
    io_threads: int = 2
    tar_prefix: str = "run"
    transport: str = "rdma_staged"   # any registered transport name
    gateway: bool = False            # addr is a staging gateway (pool mode)

    def transport_config(self, addr: str) -> TransportConfig:
        """This configuration's transport knobs, with ``addr`` as the
        staging server, the gateway, or the SAVIME endpoint of the
        copy-emulation transports."""
        staged = self.transport == "rdma_staged"
        gateway = staged and self.gateway
        knobs = {f.name: getattr(self, f.name)
                 for f in dataclasses.fields(TransportConfig)}
        knobs.update(staging_addr=addr if staged and not gateway else None,
                     savime_addr=None if staged else addr,
                     gateway_addr=addr if gateway else None)
        return TransportConfig(**knobs)


class InTransitSink:
    """Asynchronous egress of named arrays into SAVIME via a
    :class:`~repro.transport.TransferSession`.

    ``addr`` is the staging server for the default ``rdma_staged``
    transport, or the SAVIME address for the copy-emulation transports
    (``cfg.transport`` names any registered engine).
    """

    def __init__(self, addr: str, cfg: InTransitConfig = InTransitConfig()):
        self.cfg = cfg
        self.session = TransferSession(cfg.transport,
                                       cfg.transport_config(addr)).open()
        self._tars: set[str] = set()
        self._pending: list[LoadSubtar] = []  # typed DDL to run at flush
        self._lock = threading.Lock()
        self.staged_bytes = 0
        self.staged_arrays = 0

    # ------------------------------------------------------------------
    def _ensure_tar(self, tar: str, shape: tuple[int, ...],
                    dtype: str) -> None:
        if tar in self._tars:
            return
        dims = (Dimension("step", 0, MAX_STEPS),) + tuple(
            Dimension(f"d{i}", 0, n - 1) for i, n in enumerate(shape))
        self.session.run_savime(CreateTar(tar, dims, (Attribute("v", dtype),)))
        self._tars.add(tar)

    def stage_array(self, name: str, arr: Any, step: int = 0) -> None:
        """Non-blocking: device->host copy + enqueue. `arr` is a jax or
        numpy array; the write itself happens on libstaging I/O threads."""
        tar = f"{self.cfg.tar_prefix}_{name}"
        ds_name = f"{tar}__{step}"
        nbytes = int(getattr(arr, "nbytes", 0))
        with obs.span("sink.stage", ds=ds_name, bytes=nbytes):
            with obs.span("sink.d2h", ds=ds_name, bytes=nbytes):
                x = np.asarray(arr)               # device_get for jax arrays
            self._ensure_tar(tar, x.shape, str(x.dtype))
            self.session.write(ds_name, np.ascontiguousarray(x),
                               dtype=str(x.dtype))
            with self._lock:
                self._pending.append(LoadSubtar(
                    tar, ds_name, (step,) + (0,) * x.ndim,
                    (1,) + x.shape, "v"))
            self.staged_bytes += x.nbytes
            self.staged_arrays += 1

    def stage_tree(self, prefix: str, tree: Any, step: int = 0) -> None:
        import jax
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in flat:
            key = prefix + "".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path
            ).replace("/", "_").replace(".", "_").replace(":", "_")
            self.stage_array(key, leaf, step)

    def flush(self, timeout: Optional[float] = None) -> None:
        """Block until staged data is queryable in SAVIME (sync + drain +
        pending load_subtar DDL). The hot loop never calls this; analysis
        clients / checkpoint barriers do."""
        with obs.span("sink.flush") as sp:
            self.session.sync(timeout)
            self.session.drain(timeout)
            with self._lock:
                pending, self._pending = self._pending, []
            seen = set()
            for q in pending:
                # replay-after-restore stages the same step twice: the
                # dataset name is the idempotency token — run its DDL once
                if q in seen:
                    continue
                seen.add(q)
                with obs.span("sink.load_subtar", ds=q.dataset):
                    self.session.run_savime(q)
            sp.set(datasets=len(seen), pinned_bytes=self.session.held_bytes)

    def close(self) -> None:
        try:
            self.flush()
        finally:
            self.session.close()
