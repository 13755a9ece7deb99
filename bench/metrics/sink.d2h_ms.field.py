"""Mean device-to-host copy per step in the window: the program's
``sink.d2h`` spans (the ``np.asarray`` inside ``stage_array``, with any
wait for the step to be ready), summed over the window's steps."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("sink.d2h")]
    steps = run["record"].get("steps")
    return 1e3 * sum(d) / steps if d and steps else None
