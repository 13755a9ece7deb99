"""Mean encode time per dataset of the int8-block codec in the window,
from the client's own counters (TransferStats.codec)."""


def read(run):
    c = run["record"].get("codec") or {}
    if not c.get("datasets"):
        return None
    return 1e3 * c["encode_s"] / c["datasets"]
