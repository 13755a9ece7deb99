"""Mean time per flush that ``InTransitSink.flush`` waits for the client's
I/O threads to finish sending the group to staging: the program's
``session.sync`` spans inside ``sink.flush``, summed over the window's
groups."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    flushes = {s.id for s in obs.spans("sink.flush")}
    d = [s.seconds for s in obs.spans("session.sync") if s.parent in flushes]
    groups = run["record"].get("groups")
    return 1e3 * sum(d) / groups if d and groups else None
