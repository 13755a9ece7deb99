"""Time per decode step the producer spends in ``TransferSession.write``
for the step's telemetry (backpressure wait, journal and submit to the
I/O threads): the program's ``session.write`` spans, summed over the
window's decode steps."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("session.write")]
    steps = len(run["record"].get("decode_positions") or ())
    return 1e6 * sum(d) / steps if d and steps else None
