"""Operations and bytes that the ``glm-5.2-ep16`` forward pass needs,
from the sizes of ``networkSpec`` alone: a row of l tokens through
latent attention over the selected keys, the selector where a layer has
one, the gated feed-forwards, the experts held here and the head at the
last position. Counts are of what the mathematics requires: the
selected pairs only (a full causal product in their place is the
implementation's choice), no index score for a query that keeps every
key, the experts held only, padded rows are not work. Nothing here
imports the program, so the count is the same whatever implements it.
"""

from __future__ import annotations


def selected_pairs(length: int, topk: int) -> int:
    """sum_t min(t + 1, topk): the (query, key) pairs attended."""
    full = min(length, topk)
    return full * (full + 1) // 2 + max(0, length - topk) * topk


def scored_pairs(length: int, topk: int) -> int:
    """The causal pairs of the queries that have more than topk keys to
    choose from; the others keep every key and need no score."""
    if length <= topk:
        return 0
    return length * (length + 1) // 2 - topk * (topk + 1) // 2


def attention_projection_flops(s: dict, length: int) -> float:
    d, h = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    per_token = (d * s["q_lora_rank"] + s["q_lora_rank"] * h * qk
                 + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
                 + s["kv_lora_rank"] * h
                 * (s["qk_nope_head_dim"] + s["v_head_dim"])
                 + h * s["v_head_dim"] * d)
    return 2.0 * length * per_token


def attend_cost(s: dict, length: int, itemsize: int = 2) -> dict:
    """One sequence through one layer's attention over the selected
    keys: q.k and p.v for every selected pair and head; reads q, k, v
    and the table of selected keys (a byte a pair of positions), writes
    the output."""
    h = s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    dv = s["v_head_dim"]
    flops = 2.0 * selected_pairs(length, s["index_topk"]) * h * (qk + dv)
    elems = h * length * (2 * qk + 2 * dv)
    return {"flops": flops, "bytes": elems * itemsize + length * length}


def selector_flops(s: dict, length: int) -> float:
    """The selector of one 'full' layer: its three projections and the
    index scores of the pairs that need one."""
    j, di = s["index_n_heads"], s["index_head_dim"]
    if length <= s["index_topk"]:
        return 0.0
    proj = 2.0 * length * (s["q_lora_rank"] * j * di
                           + s["hidden_size"] * (di + j))
    return proj + 2.0 * scored_pairs(length, s["index_topk"]) * j * di


def gated_mlp_flops(hidden: int, width: int, tokens: float) -> float:
    return 2.0 * 3 * hidden * width * tokens


def expected_pairs_held(s: dict, length: int) -> float:
    """(token, expert) pairs that fall to the experts held, a layer and
    row, if every expert is chosen equally often."""
    return length * s["num_experts_per_tok"] * s["experts_held"] \
        / s["experts_total"]


def experts_cost(s: dict, pairs_held: float, itemsize: int = 2) -> dict:
    """The grouped products of one expert layer over ``pairs_held``
    (token, expert) pairs: gate, up and down of width
    moe_intermediate_size; reads the held experts' weights once and
    each pair's input, writes each pair's output."""
    d, w = s["hidden_size"], s["moe_intermediate_size"]
    return {"flops": gated_mlp_flops(d, w, pairs_held),
            "bytes": (s["experts_held"] * 3 * d * w
                      + pairs_held * 2 * d) * itemsize}


def forward_flops_per_row(s: dict, length: int,
                          pairs_held_per_row=None) -> float:
    """One row through every layer and the head at its last position.
    ``pairs_held_per_row`` is the count the program reports (summed
    over the expert layers); the uniform expectation where it is not
    given."""
    kinds = list(zip(s["mlp_layer_types"], s["indexer_types"]))
    sparse = sum(1 for mlp, _ in kinds if mlp == "sparse")
    if pairs_held_per_row is None:
        pairs_held_per_row = sparse * expected_pairs_held(s, length)
    d = s["hidden_size"]
    total = 0.0
    for mlp, indexer in kinds:
        total += attention_projection_flops(s, length)
        total += attend_cost(s, length)["flops"]
        if indexer == "full":
            total += selector_flops(s, length)
        if mlp == "dense":
            total += gated_mlp_flops(d, s["intermediate_size"], length)
        else:
            total += 2.0 * length * d * s["experts_total"]      # router
            total += s["n_shared_experts"] * gated_mlp_flops(
                d, s["moe_intermediate_size"], length)
    total += gated_mlp_flops(d, s["moe_intermediate_size"],
                             pairs_held_per_row)
    return total + 2.0 * d * s["vocab_size"]                     # head


def parameters(s: dict) -> int:
    """Parameters held on this chip, from the sizes."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    attn = (d * s["q_lora_rank"] + s["q_lora_rank"]
            + s["q_lora_rank"] * h * qk
            + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
            + s["kv_lora_rank"]
            + s["kv_lora_rank"] * h
            * (s["qk_nope_head_dim"] + s["v_head_dim"])
            + h * s["v_head_dim"] * d)
    j, di = s["index_n_heads"], s["index_head_dim"]
    selector = s["q_lora_rank"] * j * di + d * di + 2 * di + d * j
    w = s["moe_intermediate_size"]
    total = 2 * s["vocab_size"] * d + d
    for mlp, indexer in zip(s["mlp_layer_types"], s["indexer_types"]):
        total += attn + 2 * d + (selector if indexer == "full" else 0)
        if mlp == "dense":
            total += 3 * d * s["intermediate_size"]
        else:
            total += (s["experts_total"] * d + s["experts_total"]
                      + (s["experts_held"] + s["n_shared_experts"])
                      * 3 * d * w)
    return total
