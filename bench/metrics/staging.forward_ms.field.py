"""Mean time per dataset of staging's forward to SAVIME on its send pool,
up to SAVIME's acknowledgement: the program's ``staging.forward`` spans."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    d = [s.seconds for s in obs.spans("staging.forward")]
    return 1e3 * sum(d) / len(d) if d else None
