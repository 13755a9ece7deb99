"""The trace reduction: busy and idle time, top operations, program time
and the attribution of idle gaps to host spans."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402,F401  (puts bench/ on the path)
import harness  # noqa: E402

tr = harness.load_module(harness.BENCH / "trace.py", "bench_trace")


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]


def test_gaps_are_the_complement_within_the_window():
    busy = tr.union([(1, 2), (3, 4)])
    assert tr.gaps(busy, 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps(busy, 1.5, 3.5) == [(2, 3)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_gap_goes_to_the_span_covering_most_of_it():
    spans = [("flush", 0.0, 10.0), ("stage_array", 2.0, 3.0)]
    assert tr.attribute((2.0, 3.0), spans) == "stage_array"   # tie: shorter
    assert tr.attribute((1.0, 4.0), spans) == "flush"
    assert tr.attribute((11.0, 12.0), spans) == tr.NO_SPAN


def test_reduce_events_on_a_synthetic_window():
    ops = [[("%fusion.1 = f32[8] fusion(...)", 0.0, 1.0),
            ("%fusion.1 = f32[8] fusion(...)", 0.5, 1.5),   # overlaps
            ("%copy.2 = f32[8] copy(...)", 3.0, 4.0),
            ("%copy.2 = f32[8] copy(...)", 9.0, 12.0)]]       # clipped
    modules = [("jit_decode(123)", 0.0, 1.5), ("jit_decode(123)", 3.0, 4.0),
               ("jit_prefill(9)", 20.0, 21.0)]              # outside
    spans = [("decode_step", 0.0, 1.5), ("stage_array", 1.5, 3.0),
             ("flush", 4.0, 9.0), ("other", 0.0, 10.0)]
    red = tr.reduce_events(ops, modules, spans, (0.0, 10.0))
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(1.5 + 1.0 + 1.0)
    assert red["idle_share"] == pytest.approx(0.65)
    assert red["device_ops"][0] == ["%fusion.1", 2.0]
    assert red["modules"] == {"jit_decode": {"count": 2, "seconds": 2.5}}
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"stage_array": 1.5, "flush": 5.0})
    assert red["n_gaps"] == 2 and red["longest_gap_s"] == pytest.approx(5.0)


def test_busy_is_averaged_over_the_chips_used():
    ops = [[("a", 0.0, 1.0)], [("a", 0.0, 3.0)]]
    red = tr.reduce_events(ops, [], [], (0.0, 4.0))
    assert red["busy_s"] == pytest.approx(2.0)


def test_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("decode_step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("not_a_span"):
                    pass
    finally:
        jax.profiler.stop_trace()
    red = tr.reduce_trace(tmp_path)
    assert red is not None
    assert red["window_s"] > 0
    assert red["device_planes"] == 0          # the CPU has no device plane
    assert red["busy_s"] == 0.0
    assert [name for name, _ in red["idle_gaps"]][0] in ("decode_step",
                                                         tr.NO_SPAN)


def test_no_trace_reads_as_none(tmp_path):
    assert tr.reduce_trace(tmp_path) is None
