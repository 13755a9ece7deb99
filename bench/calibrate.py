#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for its
control, over many seeds in one process, to set each cell's limits.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 30
    python bench/calibrate.py ... --fault token

Each seed runs the cell as ``bench/run.py`` would, with a window of
``--seconds``, and prints one JSON line: the program's numbers (the run's
own checks) and the control's, which is the configuration's reference in
the program's place at the precision below the one the configuration
states (see each ``configs/<config>_ref.py``). With ``--fault`` the timed
path is broken underneath first (see ``plant_fault``), and the program's
numbers are the fault's readings. The benchmark's own runs never compute
the control and never plant a fault.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def patch_control(ref, readings: dict) -> None:
    """Wrap the reference's comparisons so each also records the number
    the control would have read on the same inputs."""
    if hasattr(ref, "served_logits"):
        plain_logits, plain_judge = ref.served_logits, ref.judge
        inputs = {}

        def served_logits(w, prompts, served, m, quant=None):
            inputs.update(w=w, prompts=prompts, served=served, m=m)
            return plain_logits(w, prompts, served, m, quant=quant)

        def judge(ref_logits, tokens, logits):
            ref.served_logits, ref.judge = plain_logits, plain_judge
            try:
                readings.update(ref.control(
                    inputs["w"], inputs["prompts"], inputs["served"],
                    inputs["m"], ref_logits))
            finally:
                ref.served_logits, ref.judge = served_logits, judge
                inputs.clear()        # frees this seed's weights
            return plain_judge(ref_logits, tokens, logits)
        ref.served_logits, ref.judge = served_logits, judge
        return

    mism, over = ref.mismatched, ref.err_over_bound

    def mismatched(got, want):
        readings["mismatched_values"] = readings.get(
            "mismatched_values", 0) + mism(
            ref.control(want, "none", None, None), want)
        return mism(got, want)

    def err_over_bound(got, want, flat, amax):
        ctl = ref.control(want, "int8-block", amax, flat)
        readings["int8_err_over_bound"] = max(
            readings.get("int8_err_over_bound", 0.0),
            over(ctl, want, flat, amax))
        return over(got, want, flat, amax)

    ref.mismatched, ref.err_over_bound = mismatched, err_over_bound


def plant_fault(fault: str, traffic: dict):
    """Break the timed path underneath; returns a function that mends it.

    token      every decode step serves token 7 (a token altered where it
               is produced)
    cache      decode returns its cache unchanged (a step that returns its
               state unchanged)
    answer     one value inside the analyst's box is changed on its way
               into the sink (an answer altered where it is produced)
    stale      every field step stages the first step's values
    telemetry  every staged latency is one off
    """
    import numpy as np
    from repro.core import InTransitSink
    from repro.train import ServeSetup

    if fault in ("token", "cache"):
        real = ServeSetup.decode_fn

        def decode_fn(self):
            step = real(self)

            def decode(params, cache, batch):
                logits, new_cache = step(params, cache, batch)
                if fault == "token":
                    return logits.at[:, 7].set(1e4), new_cache
                return logits, cache
            return decode
        ServeSetup.decode_fn = decode_fn
        return lambda: setattr(ServeSetup, "decode_fn", real)

    real_stage = InTransitSink.stage_array
    first = {}

    def change(name, x):
        if fault == "answer":
            x = np.array(x)
            x[tuple(traffic["query_lo"])] += 1.0
            return x
        if fault == "stale":
            return first.setdefault(name[:1], np.array(x))
        if fault == "telemetry" and name == "decode_ms":
            return np.asarray(x) + 1
        return x

    def stage_array(self, name, arr, step=0):
        return real_stage(self, name, change(name, arr), step)
    InTransitSink.stage_array = stage_array
    return lambda: setattr(InTransitSink, "stage_array", real_stage)


FAULTS = ("token", "cache", "answer", "stale", "telemetry")


@contextlib.contextmanager
def recording_control(readings: dict):
    """While open, every run's reference also records the control's
    numbers into ``readings``."""
    real_load = harness.load_module

    def load_module(path, name=None):
        mod = real_load(path, name)
        if path.name.endswith("_ref.py"):
            patch_control(mod, readings)
        return mod

    harness.load_module = load_module
    try:
        yield readings
    finally:
        harness.load_module = real_load


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=FAULTS, default=None)
    args = ap.parse_args(argv)

    harness.setup_jax_env()
    readings: dict = {}
    if args.fault:
        bench = harness.load_benchmark()
        harness.setup_paths()
        plant_fault(args.fault, harness.resolve_cell(
            args.workload, bench)["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        readings.clear()
        t = time.perf_counter()
        with recording_control(readings):
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   log=lambda s: print(s, file=sys.stderr))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "program": {k: c["value"] for k, c in res["checks"].items()},
            "control": dict(readings),
            "correct": res["correct"], "seconds": time.perf_counter() - t,
            "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
