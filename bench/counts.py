"""The yardstick's arithmetic: peaks by device kind, and the operations and
least bytes of a decoder-only transformer's prefill and decode, computed
from a configuration's sizes (its ``model`` group).

Operations count multiply-adds as two. Least bytes are what a step must
move at the configuration's compute dtype whatever implements it: every
matrix weight read once, every cached key and value up to the position
read once, and the new key and value written once.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


class UnknownDevice(KeyError):
    """A device kind the table of peaks does not hold."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def matmul_params(m: dict) -> int:
    """Weights that take part in a matrix product per token: attention
    projections and MLP of every layer, and the output head. The embedding
    table is a lookup and the norms are elementwise, so neither counts."""
    M, F = m["d_model"], m["d_ff"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    attn = M * q * 2 + M * kv * 2
    mlp = M * F * (3 if m.get("mlp_glu") else 2)
    return m["n_layers"] * (attn + mlp) + M * m["vocab_size"]


def param_count(m: dict) -> int:
    """Every parameter: matrix weights, embedding table, norm scales."""
    M = m["d_model"]
    return (matmul_params(m) + m["vocab_size"] * M
            + m["n_layers"] * 2 * M + M)


def kv_bytes_per_token(m: dict) -> int:
    """Cached key and value bytes of one position across all layers."""
    return (2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m["compute_dtype"]])


def attn_flops(m: dict, n_keys: int) -> int:
    """Scores and weighted values of one query over n_keys, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * n_keys


def decode_flops(m: dict, positions) -> int:
    """One decode step: one new token per request, at the given positions
    (0-based), each attending over positions 0..pos."""
    return sum(2 * matmul_params(m) + attn_flops(m, p + 1) for p in positions)


def decode_bytes(m: dict, positions) -> int:
    """Least bytes of one decode step over a batch at these positions."""
    w = matmul_params(m) * DTYPE_BYTES[m["compute_dtype"]]
    kv = kv_bytes_per_token(m)
    return w + sum((p + 1) * kv for p in positions)


def prefill_flops(m: dict, batch: int, length: int) -> int:
    """A causal prefill of `length` tokens per request: the projections of
    every position, attention over the causal triangle, and the output head
    of the last position only (as the served prefill computes it)."""
    M, V = m["d_model"], m["vocab_size"]
    per_tok = 2 * (matmul_params(m) - M * V)
    tri = sum(attn_flops(m, k + 1) for k in range(length))
    return batch * (length * per_tok + tri + 2 * M * V)
