"""Operations and bytes that the benchmark's work requires, from shapes
alone, and the table of chip peaks they are divided by."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row of one chip; a chip missing from the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dense_matmul_params(m: dict) -> int:
    """Weights that every token multiplies in a llama-style decoder with
    grouped-query attention and a gated MLP: the projections of each layer
    and the output head over the published vocabulary.  The input
    embedding is a lookup and costs no multiplication."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + d * m["vocab_size"]


def mean_context(seq: int, window: int | None) -> float:
    """Keys each query attends to, averaged over the positions of a causal
    sequence of length ``seq`` with an optional sliding window."""
    w = window or seq
    return sum(min(t + 1, w) for t in range(seq)) / seq


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    weight, and 3 x 4 * heads * head_dim per attended key for the score and
    value products.  Recomputation is not counted."""
    attn = 12 * m["num_heads"] * m["head_dim"] * mean_context(
        seq, m.get("sliding_window"))
    return 6.0 * dense_matmul_params(m) + m["num_layers"] * attn


def alltoall_egress_bytes(buffer_bytes: int, group: int) -> float:
    """Bytes that must leave each chip in an alltoall over ``group`` chips:
    every block but the one the chip keeps for itself."""
    return buffer_bytes * (group - 1) / group
