"""A run whose timed path is broken underneath must come out not correct:
the harness's look for a chip is skipped and the rest of a run is driven
at a small size on the CPU, once for each fault a cell can have."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402
import calibrate  # noqa: E402

FIELDS = ["seismic.f32.stream", "seismic.int8.stream"]
SERVES = ["musicgen.decode.telemetry", "musicgen.decode.bare"]
CASES = ([(w, f) for w in FIELDS for f in ("answer", "stale")]
         + [(w, f) for w in SERVES for f in ("token", "cache")]
         + [("musicgen.decode.telemetry", "telemetry")])


@pytest.mark.parametrize("workload,fault", CASES)
def test_broken_timed_path_is_not_correct(workload, fault):
    mend = calibrate.plant_fault(fault, _cells.small(workload)["traffic"])
    try:
        res = _cells.run(workload)
    finally:
        mend()
    assert res["correct"] is False, res["checks"]
    if fault == "telemetry":
        assert res["checks"]["telemetry_mismatch"]["value"] > 0


def test_every_fault_is_mended():
    from repro.core import InTransitSink
    from repro.train import ServeSetup
    before = (InTransitSink.stage_array, ServeSetup.decode_fn)
    for fault in calibrate.FAULTS:
        calibrate.plant_fault(fault, _cells.FIELD["traffic"])()
    assert (InTransitSink.stage_array, ServeSetup.decode_fn) == before
