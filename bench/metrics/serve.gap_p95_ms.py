"""95th percentile of the host time of one decode step, from dispatching
the decode program to the sampled token being ready."""
from harness import quantile


def read(run):
    lat = run["record"].get("step_latency_s")
    return quantile(lat, 0.95) * 1e3 if lat else None
