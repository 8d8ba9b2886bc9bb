"""Operations and bytes that the ``lfm2-24b-a2b-stage`` forward pass
needs, from the sizes of ``networkSpec`` alone: a row of l tokens
through gated short convolutions or causal grouped-query attention, a
gated feed-forward or the routed experts after each, and the tied head
at the last position. Counts are of what the mathematics requires: the
causal pairs only, ``num_experts_per_tok`` experts a token (or the
pairs the program counted), K and V read once a key/value head, padded
rows are not work; element-wise work (norms, rotary, the convolution's
gates and taps) is not counted. Nothing here imports the program, so the
count is the same whatever implements it.
"""

from __future__ import annotations


def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def sparse_layers(s: dict) -> int:
    return len(s["layer_types"]) - s["num_dense_layers"]


def head_dim(s: dict) -> int:
    return s["hidden_size"] // s["num_attention_heads"]


def conv_params(s: dict) -> int:
    """W_in d x 3d, the taps L x d, W_out d x d."""
    d = s["hidden_size"]
    return 4 * d * d + s["conv_L_cache"] * d


def attention_params(s: dict) -> int:
    """W_q and W_o d x H D, W_k and W_v d x Hkv D, the two head norms."""
    d, width = s["hidden_size"], head_dim(s)
    return (2 * d * s["num_attention_heads"] * width
            + 2 * d * s["num_key_value_heads"] * width + 2 * width)


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def parameters(s: dict) -> int:
    """Parameters held on this chip, from the sizes (the embedding is
    the head too: counted once)."""
    d = s["hidden_size"]
    total = s["vocab_size"] * d + d
    for i, kind in enumerate(s["layer_types"]):
        total += 2 * d + (conv_params(s) if kind == "conv"
                          else attention_params(s))
        if i < s["num_dense_layers"]:
            total += 3 * d * s["intermediate_size"]
        else:
            total += s["num_experts"] * (d + 1 + expert_params(s))
    return total


def gated_mlp_flops(hidden: int, width: int, tokens: float) -> float:
    return 2.0 * 3 * hidden * width * tokens


def flash_cost(s: dict, batch: int, length: int, itemsize: int = 2) -> dict:
    """One causal grouped-query forward call over ``batch`` rows: q.k
    and p.v for every causal pair and query head; reads q, and k and v
    once a key/value head, writes o and the float32 row sums."""
    h, hk, width = (s["num_attention_heads"], s["num_key_value_heads"],
                    head_dim(s))
    flops = 2.0 * 2 * batch * h * causal_pairs(length) * width
    elems = batch * length * width * (2 * h + 2 * hk)
    return {"flops": flops,
            "bytes": elems * itemsize + 4 * batch * h * length}


def experts_cost(s: dict, pairs: float, itemsize: int = 2) -> dict:
    """The grouped products of one expert layer over ``pairs`` (token,
    expert) pairs: gate, up and down of width moe_intermediate_size;
    reads every expert's weights once and each pair's input, writes each
    pair's output."""
    d = s["hidden_size"]
    return {"flops": gated_mlp_flops(d, s["moe_intermediate_size"], pairs),
            "bytes": (s["num_experts"] * expert_params(s)
                      + pairs * 2 * d) * itemsize}


def expected_pairs(s: dict, length: int) -> float:
    """(token, expert) pairs a row routes, summed over the expert
    layers: every one of them lands here."""
    return float(sparse_layers(s) * length * s["num_experts_per_tok"])


def forward_flops_per_row(s: dict, length: int, pairs_per_row=None
                          ) -> float:
    """One row through every layer and the head at its last position.
    ``pairs_per_row`` is the count the program reports (summed over the
    expert layers); ``num_experts_per_tok`` a token where not given."""
    d = s["hidden_size"]
    if pairs_per_row is None:
        pairs_per_row = expected_pairs(s, length)
    total = 0.0
    for i, kind in enumerate(s["layer_types"]):
        if kind == "conv":
            total += 2.0 * length * 4 * d * d
        else:
            total += 2.0 * length * (attention_params(s) - 2 * head_dim(s))
            total += flash_cost(s, 1, length)["flops"]
        if i < s["num_dense_layers"]:
            total += gated_mlp_flops(d, s["intermediate_size"], length)
        else:
            total += 2.0 * length * d * s["num_experts"]        # router
    total += gated_mlp_flops(d, s["moe_intermediate_size"], pairs_per_row)
    return total + 2.0 * d * s["vocab_size"]                     # head
