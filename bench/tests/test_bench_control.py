"""Each cell's control, the configuration's reference put in the program's
place one precision below the configuration's, must come out not correct,
while the program itself is correct on the same run. At a small size on
the CPU; the readings at the cells' own sizes are in PERF.md."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402
import calibrate  # noqa: E402
import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    with calibrate.recording_control({}) as readings:
        res = _cells.run(workload)
    assert res["correct"] is True, res["checks"]
    limits = {k: c["limit"] for k, c in res["checks"].items()}
    failed = [k for k, v in readings.items()
              if k in limits and v > limits[k]]
    assert failed, (readings, limits)
