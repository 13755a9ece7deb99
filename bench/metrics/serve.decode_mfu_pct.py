"""Whole-step model FLOP/s utilization of serving: the operations every
prefill and decode step of the traced window needs, from the
configuration's shapes, over the window and the chip's bf16 peak."""
import counts


def read(run):
    rec = run["record"]
    if not rec.get("decode_positions") or not (run["trace"] or {}).get(
            "device_planes"):
        return None
    m, B = run["config"]["model"], rec["batch"]
    flops = rec["prefills"] * counts.prefill_flops(m, B, rec["prompt_len"])
    flops += sum(counts.decode_flops(m, [p] * B)
                 for p in rec["decode_positions"])
    peak = counts.peaks(run["device"]["kind"])["bf16_flops"]
    return 100.0 * flops / (rec["window_s"] * peak)
