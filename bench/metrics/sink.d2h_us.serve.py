"""Device-to-host copy time per decode step of the step's telemetry (its
latency and one logits row): the program's ``sink.d2h`` spans, summed
over the window's decode steps."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("sink.d2h")]
    steps = len(run["record"].get("decode_positions") or ())
    return 1e6 * sum(d) / steps if d and steps else None
