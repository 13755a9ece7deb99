"""Small sizes at which the tests run the cells on the CPU."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

harness.setup_paths()

FIELD = {"config": {"mesh": [12, 20, 24], "staging_mem_bytes": 64 << 20},
         "traffic": {"query_lo": [2, 3, 4], "query_hi": [6, 10, 12],
                     "query_rate_hz": 20}}
SERVE = {"config": {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                              "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                              "vocab_size": 256}},
         "traffic": {"batch": 2, "prompt_len": 8, "new_tokens": 6}}
SEED = 2 ** 33 + 7          # above 32 bits, as seeds may be


def small(workload: str) -> dict:
    return FIELD if workload.startswith("seismic.") else SERVE


def run(workload: str, seconds: float = 1.0, trace: bool = False,
        overrides=None, seed: int = SEED) -> dict:
    return harness.run_cell(workload, seed, seconds, trace,
                            require_tpu=False, compile_cache=False,
                            overrides=harness.deep_merge(small(workload),
                                                         overrides),
                            log=lambda s: None)
