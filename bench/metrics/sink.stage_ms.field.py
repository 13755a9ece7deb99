"""Mean wall time of InTransitSink.stage_array per field step in the
window: the device-to-host copy and the enqueue of the write."""


def read(run):
    d = run["record"]["spans"].get("stage_array")
    return 1e3 * sum(d) / len(d) if d else None
