"""Operations and bytes the algorithm needs, from shapes alone.

Counts are of what the mathematics requires: the causal half of
attention, no recomputation, padded rows are not work. A faster kernel
therefore cannot push a share past its peak. Nothing here imports the
program.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a kind that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def head_width(spec: dict) -> int:
    return spec.get("num_classes") or spec["vocab_size"]


def block_matmul_params(spec: dict) -> int:
    """qkv d x 3d, proj d x d, mlp d x 4d and 4d x d, in every block."""
    return 12 * spec["dim"] ** 2 * spec["depth"]


def total_params(spec: dict) -> int:
    d, depth = spec["dim"], spec["depth"]
    per_block = 12 * d * d + 13 * d          # kernels, biases, two norms
    return (spec["vocab_size"] * d + spec["max_len"] * d
            + depth * per_block + 2 * d
            + d * head_width(spec) + head_width(spec))


def attention_forward_flops(batch: int, heads: int, seq: int,
                            head_dim: int) -> float:
    """QK^T and PV, each 2*S*S*D a head, causal half."""
    return 2 * 2 * batch * heads * seq * seq * head_dim / 2


def train_flops_per_token(spec: dict, seq: int) -> float:
    """6 x matmul parameters (LM head included) + causal attention
    forward and backward (3 x forward) = 6*S*d a layer and token."""
    matmul = block_matmul_params(spec) + spec["dim"] * head_width(spec)
    attn = 3 * attention_forward_flops(1, spec["heads"], seq,
                                       spec["dim"] // spec["heads"]) / seq
    return 6 * matmul + attn * spec["depth"]


def forward_flops_per_row(spec: dict, seq: int) -> float:
    """One sequence through the trunk and its head. A classifier head
    reads one pooled vector, an LM head every token."""
    attn = attention_forward_flops(1, spec["heads"], seq,
                                   spec["dim"] // spec["heads"])
    head_rows = 1 if spec.get("num_classes") else seq
    return (2 * block_matmul_params(spec) * seq + attn * spec["depth"]
            + 2 * spec["dim"] * head_width(spec) * head_rows)


def flash_forward_cost(batch: int, heads: int, seq: int, head_dim: int,
                       itemsize: int = 2) -> dict:
    """One forward call: reads q, k, v, writes o and the f32 row sums."""
    elems = batch * heads * seq * head_dim
    return {"flops": attention_forward_flops(batch, heads, seq, head_dim),
            "bytes": 4 * elems * itemsize + 4 * batch * heads * seq}


def flash_backward_cost(batch: int, heads: int, seq: int, head_dim: int,
                        itemsize: int = 2) -> dict:
    """The backward of one call: dV, dP, dQ, dK are four matmuls of the
    forward's size (2 x forward); recomputing the scores is the
    kernel's choice and is not counted. Reads q, k, v, o, do, writes
    dq, dk, dv."""
    elems = batch * heads * seq * head_dim
    return {"flops": 2 * attention_forward_flops(batch, heads, seq,
                                                 head_dim),
            "bytes": 8 * elems * itemsize + 8 * batch * heads * seq}


def roofline_seconds(cost: dict, peak: dict) -> dict:
    """The least time the chip could take, and which limit sets it."""
    t_flops = cost["flops"] / peak["bf16_flops"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
