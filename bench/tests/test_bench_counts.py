"""The yardstick's arithmetic against hand counts, the table of peaks, and
the benchmark file against the rules its names and units must keep."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _cells  # noqa: E402,F401  (puts bench/ on the path)
import counts  # noqa: E402
import harness  # noqa: E402

BENCH = harness.load_benchmark()
MUSICGEN = json.loads(
    (harness.BENCH / "configs" / "musicgen-medium-serve.json").read_text())
M = MUSICGEN["model"]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_musicgen_medium_parameters_by_hand():
    per_layer = 4 * 1536 * 1536 + 2 * 1536 * 6144       # attention + MLP
    assert counts.matmul_params(M) == 48 * per_layer + 1536 * 2048
    assert counts.param_count(M) == (48 * per_layer + 2 * 1536 * 2048
                                     + 48 * 2 * 1536 + 1536)
    assert counts.param_count(M) == 1_365_394_944      # about 1.365 B


def test_musicgen_kv_bytes_per_token_by_hand():
    # key and value, 48 layers, 24 heads of 64, bfloat16
    assert counts.kv_bytes_per_token(M) == 2 * 48 * 24 * 64 * 2 == 294_912


def test_decode_counts_by_hand():
    n = counts.matmul_params(M)
    # one request at position 9 attends over 10 keys in every layer
    assert counts.decode_flops(M, [9]) == 2 * n + 4 * 48 * 24 * 64 * 10
    assert counts.decode_bytes(M, [9]) == 2 * n + 10 * 294_912
    both = counts.decode_flops(M, [9, 99])
    assert both == counts.decode_flops(M, [9]) + counts.decode_flops(M, [99])


def test_prefill_counts_match_a_brute_force_sum():
    m = dict(M, n_layers=2, d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
             d_ff=16, vocab_size=32)
    n = counts.matmul_params(m)
    head = 8 * 32
    brute = 0
    for k in range(5):                       # five positions, causal
        brute += 2 * (n - head) + 4 * 2 * 2 * 4 * (k + 1)
    brute += 2 * head                        # logits of the last position
    assert counts.prefill_flops(m, 3, 5) == 3 * brute


def test_peaks_are_keyed_by_device_kind():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_an_unknown_device_kind_is_refused(kind):
    with pytest.raises(counts.UnknownDevice):
        counts.peaks(kind)


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[key]:
            yield key, e


@pytest.mark.parametrize("key,entry", list(_names()),
                         ids=lambda v: v if isinstance(v, str) else v["name"])
def test_names_and_units_use_allowed_characters(key, entry):
    assert NAME.match(entry["name"]), entry["name"]
    for k in ("config", "traffic"):
        if k in entry:
            assert NAME.match(entry[k]), entry[k]
    for k in entry.get("reduced", []):
        assert NAME.match(k), k
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k] \
                and "\t" not in entry[k], (k, entry[k])


def test_every_name_is_unique_and_every_file_is_there():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert (harness.BENCH / "configs" / f"{c['name']}_ref.py").is_file()
    for w in BENCH["workloads"]:
        tr = json.loads((harness.BENCH / "traffic"
                         / f"{w['traffic']}.json").read_text())
        assert (harness.BENCH / "drivers" / f"{tr['driver']}.py").is_file()
        assert w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        mine = {m["name"] for m in harness.cell_metrics(
            w["name"], BENCH, "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layers = harness.cell_metrics(w["name"], BENCH, "per_layer")
        assert layers, w["name"]
        for m in layers:
            assert m["moves"] in mine, (w["name"], m["name"])


def test_bounds_and_run_length_are_within_the_rules():
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


def test_roofline_and_mfu_metrics_are_named_as_shares():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
