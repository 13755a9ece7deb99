"""Share of its roofline that the compiled decode program reaches: the
least time a decode step needs on this chip (the larger of its operations
over the bf16 peak and its least bytes over HBM bandwidth, at the
configuration's compute dtype) over the device time of the program
``jit_decode`` in the trace."""
import counts

PROGRAM = "jit_decode"


def read(run):
    rec, tr = run["record"], run["trace"]
    if not (tr or {}).get("device_planes") or PROGRAM not in tr["modules"] \
            or not rec.get("decode_positions"):
        return None
    m, B = run["config"]["model"], rec["batch"]
    pk = counts.peaks(run["device"]["kind"])
    least = [max(counts.decode_flops(m, [p] * B) / pk["bf16_flops"],
                 counts.decode_bytes(m, [p] * B) / pk["hbm_bytes_per_s"])
             for p in rec["decode_positions"]]
    mod = tr["modules"][PROGRAM]
    per_step = sum(least) / len(least)
    return 100.0 * per_step * mod["count"] / mod["seconds"]
