"""Mean time per flush that ``InTransitSink.flush`` spends attaching the
group's steps in SAVIME, one ``LoadSubtar`` each: the program's
``sink.load_subtar`` spans inside ``sink.flush``, summed over the
window's groups."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    flushes = {s.id for s in obs.spans("sink.flush")}
    d = [s.seconds for s in obs.spans("sink.load_subtar")
         if s.parent in flushes]
    groups = run["record"].get("groups")
    return 1e3 * sum(d) / groups if d and groups else None
