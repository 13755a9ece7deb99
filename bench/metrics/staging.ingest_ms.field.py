"""Mean time per dataset staging takes to finish receiving it: the
program's ``staging.ingest`` spans (accounting, the int8 decode when the
codec decodes at ingest, and queueing the forward)."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("staging.ingest")]
    return 1e3 * sum(d) / len(d) if d else None
