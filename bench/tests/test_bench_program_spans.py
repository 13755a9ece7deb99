"""The per-layer metrics that read the program's own spans (repro.obs):
a traced run of one small field cell and one small serve cell reads
each of them, an untraced run records no span, and the flush's three
waits fit inside the benchmark's own clock around the flush."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402
import harness  # noqa: E402

BENCH = harness.load_benchmark()
SPAN_METRICS = {
    "sink.d2h_ms.field", "client.send_ms.field", "flush.send_wait_ms.field",
    "flush.forward_wait_ms.field", "flush.load_subtar_ms.field",
    "staging.ingest_ms.field", "staging.forward_ms.field",
    "savime.select_p95_ms.field", "sink.d2h_us.serve",
    "session.write_us.serve"}
FLUSH_PARTS = ("flush.send_wait_ms.field", "flush.forward_wait_ms.field",
               "flush.load_subtar_ms.field")
FIELD_AND_SERVE = ("seismic.f32.stream", "musicgen.decode.telemetry")


def span_metrics(workload):
    return {m["name"] for m in harness.cell_metrics(workload, BENCH,
                                                    "per_layer")
            if m["name"] in SPAN_METRICS}


def test_every_span_metric_is_read_by_some_cell():
    read = set()
    for w in BENCH["workloads"]:
        read |= span_metrics(w["name"])
    assert read == SPAN_METRICS


@pytest.mark.parametrize("workload", FIELD_AND_SERVE)
def test_traced_run_reads_each_span_metric(workload):
    res = _cells.run(workload, seconds=1.0, trace=True)
    assert res["correct"] is True, res["checks"]
    want = span_metrics(workload)
    assert want
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert want <= set(got), want - set(got)
    assert all(got[k] > 0 for k in want), got
    if workload.startswith("seismic."):
        # the three waits nest inside the flush the benchmark times
        assert sum(got[k] for k in FLUSH_PARTS) <= got["sink.flush_ms.field"]


@pytest.mark.parametrize("workload", FIELD_AND_SERVE)
def test_untraced_run_records_no_span(workload):
    from repro import obs
    before = obs.spans()
    res = _cells.run(workload, seconds=1.0, trace=False)
    assert res["correct"] is True, res["checks"]
    assert not span_metrics(workload) & set(res["metrics"])
    assert obs.spans() == before
