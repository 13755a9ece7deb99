"""The launchers' shared egress set-up, and the sink's configuration.

The launchers run end to end at smoke size on the CPU, with the compile
cache and the checkpoints under the test's temporary directory.
"""
import numpy as np
import pytest

from repro.core import InTransitConfig, InTransitSink
from repro.transport import TransportConfig

ADDR = "127.0.0.1:1"
TRANSPORT_DEFAULTS = {"block_size": 16 << 20, "io_threads": 2}


@pytest.mark.parametrize("cfg,want", [
    (InTransitConfig(),
     TransportConfig(staging_addr=ADDR, **TRANSPORT_DEFAULTS)),
    # the benchmark's decode telemetry sink
    (InTransitConfig(tar_prefix="serve"),
     TransportConfig(staging_addr=ADDR, **TRANSPORT_DEFAULTS)),
    # the benchmark's field sinks, f32 and int8
    (InTransitConfig(tar_prefix="field", transport="rdma_staged",
                     block_size=16777216, io_threads=2, codec="none"),
     TransportConfig(staging_addr=ADDR, **TRANSPORT_DEFAULTS)),
    (InTransitConfig(tar_prefix="field", transport="rdma_staged",
                     block_size=16777216, io_threads=2, codec="int8-block"),
     TransportConfig(staging_addr=ADDR, codec="int8-block",
                     **TRANSPORT_DEFAULTS)),
    (InTransitConfig(gateway=True, tenant="acme", n_channels=4),
     TransportConfig(gateway_addr=ADDR, tenant="acme", n_channels=4,
                     **TRANSPORT_DEFAULTS)),
    (InTransitConfig(transport="scp_mem", gateway=True),
     TransportConfig(savime_addr=ADDR, **TRANSPORT_DEFAULTS)),
])
def test_sink_session_config_matches_the_field_by_field_copy(
        monkeypatch, cfg, want):
    """What reaches the session equals, field by field, the TransportConfig
    the sink used to copy out of its own fields: same defaults, same
    addresses."""
    seen = []

    class Recorder:
        def __init__(self, transport, tcfg):
            seen.append((transport, tcfg))

        def open(self):
            return self

    monkeypatch.setattr("repro.core.intransit.TransferSession", Recorder)
    InTransitSink(ADDR, cfg)
    (transport, got), = seen
    assert transport == cfg.transport
    assert type(got) is TransportConfig and got == want


@pytest.fixture
def launch_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.chdir(tmp_path)
    return tmp_path


@pytest.mark.parametrize("extra", [[], ["--pool", "2"]])
def test_serve_stages_its_decode_latencies(launch_dir, extra):
    from repro.launch import serve
    s = serve.main(["--arch", "musicgen-medium", "--smoke", "--intransit",
                    "--analyzer", "running_stats"] + extra)
    staged = np.asarray(s["staged_decode_ms"], np.float32).reshape(-1)
    assert staged.size == 31
    assert np.array_equal(staged, s["decode_ms"].astype(np.float32))
    assert s["analyzer"]["count"] == 31


@pytest.mark.parametrize("extra", [[], ["--codec", "int8-block"]])
def test_train_stages_its_checkpoints(launch_dir, extra):
    from repro.launch import train
    s = train.main(["--arch", "musicgen-medium", "--smoke", "--steps", "2",
                    "--intransit", "--ckpt-dir", str(launch_dir / "ckpt")]
                   + extra)
    assert len(s["losses"]) == 2 and s["restarts"] == 0
    assert s["staged_bytes"] > 0
