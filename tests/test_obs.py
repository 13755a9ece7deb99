"""The span recorder (repro.obs): records exist only while a profiler
session traces the process, nest per thread, keep worker threads' spans
with their attributes, belong to one session, and stay within a bound."""
import contextlib
import os
import subprocess
import sys
import threading

import jax

from repro import obs


@contextlib.contextmanager
def profiling(path):
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_records_exist_only_while_a_session_is_active(tmp_path):
    with obs.span("t.before"):
        pass
    with profiling(tmp_path / "a"):
        with obs.span("t.during", n=1):
            pass
    with obs.span("t.after"):
        pass
    assert [s.name for s in obs.spans()] == ["t.during"]
    rec, = obs.spans("t.during")
    assert rec.attrs == {"n": 1} and rec.t1 >= rec.t0
    assert obs.dropped() == 0


def test_parents_nest_on_one_thread(tmp_path):
    with profiling(tmp_path):
        with obs.span("t.outer"):
            with obs.span("t.mid"):
                with obs.span("t.inner"):
                    pass
            with obs.span("t.sibling") as sib:
                sib.set(late=True)
        with obs.span("t.top"):
            pass
    by = {s.name: s for s in obs.spans()}
    assert by["t.outer"].parent is None and by["t.top"].parent is None
    assert by["t.mid"].parent == by["t.outer"].id
    assert by["t.inner"].parent == by["t.mid"].id
    assert by["t.sibling"].parent == by["t.outer"].id
    assert by["t.sibling"].attrs == {"late": True}
    # records land as spans finish: innermost first
    assert [s.name for s in obs.spans()] == ["t.inner", "t.mid", "t.sibling",
                                             "t.outer", "t.top"]


def test_worker_thread_spans_are_kept_with_their_attrs(tmp_path):
    def work(i):
        with obs.span("t.work", ds=f"d{i}", bytes=i) as sp:
            sp.set(bytes_out=2 * i)

    with profiling(tmp_path):
        with obs.span("t.main"):
            threads = [threading.Thread(target=work, args=(i,),
                                        name=f"t-worker-{i}")
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
    assert not any(t.is_alive() for t in threads)
    work_spans = sorted(obs.spans("t.work"), key=lambda s: s.attrs["bytes"])
    assert [s.thread for s in work_spans] == [f"t-worker-{i}"
                                              for i in range(4)]
    assert [s.attrs for s in work_spans] == [
        {"ds": f"d{i}", "bytes": i, "bytes_out": 2 * i} for i in range(4)]
    # another thread's stack is its own: no parent across threads
    assert all(s.parent is None for s in work_spans)


def test_a_new_session_drops_the_old_ones_records(tmp_path):
    with profiling(tmp_path / "a"):
        with obs.span("t.first"):
            pass
    assert [s.name for s in obs.spans()] == ["t.first"]
    with profiling(tmp_path / "b"):
        assert obs.spans() == []          # nothing recorded in this one yet
        with obs.span("t.second"):
            pass
    assert [s.name for s in obs.spans()] == ["t.second"]


def test_the_bound_counts_what_it_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 3)
    with profiling(tmp_path):
        for i in range(5):
            with obs.span("t.many", i=i):
                pass
    assert [s.attrs["i"] for s in obs.spans()] == [0, 1, 2]
    assert obs.dropped() == 2


def test_spans_without_jax_never_import_it():
    code = ("import sys\n"
            "from repro import obs\n"
            "import repro.core.staging, repro.core.savime\n"
            "with obs.span('t.x', a=1) as sp:\n"
            "    sp.set(b=2)\n"
            "assert obs.spans() == [] and obs.dropped() == 0\n"
            "assert 'jax' not in sys.modules\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
