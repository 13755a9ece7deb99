"""p95 over the window of SAVIME's own time for a select on its serve
thread: the program's ``savime.select`` spans, without the wire or any
wait before the query reached SAVIME."""
from harness import quantile


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("savime.select")]
    return quantile(d, 0.95) * 1e3 if d else None
