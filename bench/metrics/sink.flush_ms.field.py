"""Mean wall time of InTransitSink.flush per group in the window: the
client's send tail, staging ingest, the forward to SAVIME and the
load_subtar of each step."""


def read(run):
    d = run["record"]["spans"].get("flush")
    return 1e3 * sum(d) / len(d) if d else None
