"""Mean wall time per decode step of the step's telemetry: staging its
latency and one logits row through InTransitSink.stage_array."""


def read(run):
    d = run["record"]["spans"].get("stage_array")
    return 1e6 * sum(d) / len(d) if d else None
