"""Operations and bytes that the ``trinity-mini-stage`` forward pass
needs, from the sizes of ``networkSpec`` alone: a row of l tokens
through grouped-query attention layers with five projections each (q,
k, v, the output gate, o), "sliding_attention" ones (a query sees its
``sliding_window`` newest keys, itself among them) and "full_attention"
ones (every earlier key), a gated feed-forward after the first
``num_dense_layers`` and after the others the routed experts with
``num_shared_experts`` shared ones on every token, and the untied head
at the last position. Counts are of what the mathematics requires: the
banded pairs of a sliding layer and the causal pairs of a full one,
``num_experts_per_tok`` experts a token (or the pairs the program
counted), K and V read once a key/value head, padded rows are not work;
element-wise work (the four norms, rotary, both sigmoids) is not
counted. The pair counts, the flash call's and the grouped products'
costs are ``flops_mellum2``'s, which read the same keys. Nothing here
imports the program, so the count is the same whatever implements it.
"""

from __future__ import annotations

from flops_mellum2 import (  # noqa: F401  (readers take them from here)
    banded_pairs, causal_pairs, experts_cost, expert_params, flash_cost,
    gated_mlp_flops, head_dim, layers_of, pairs_of)


def expert_layers(s: dict) -> int:
    return max(0, len(s["layer_types"]) - s.get("num_dense_layers", 0))


def gate_params(s: dict) -> int:
    """W_g d x H D where attention has an output gate."""
    return s["hidden_size"] * s["num_attention_heads"] * head_dim(s) \
        if s.get("attention_output_gate") else 0


def attention_params(s: dict) -> int:
    """W_q and W_o d x H D, W_k and W_v d x Hkv D, the gate's W_g, the
    two head norms."""
    d, width = s["hidden_size"], head_dim(s)
    return (2 * d * s["num_attention_heads"] * width
            + 2 * d * s["num_key_value_heads"] * width + gate_params(s)
            + 2 * width)


def norm_params(s: dict) -> int:
    """A layer's gains: two, or four with the post norms."""
    return (4 if s.get("sandwich_norms") else 2) * s["hidden_size"]


def shared_params(s: dict) -> int:
    return s.get("num_shared_experts", 0) * expert_params(s)


def dense_layer_params(s: dict) -> int:
    return attention_params(s) + norm_params(s) \
        + 3 * s["hidden_size"] * s["intermediate_size"]


def expert_layer_params(s: dict) -> int:
    """An attention operator, the norms, the router and its bias, every
    routed expert and the shared ones."""
    d = s["hidden_size"]
    bias = s["num_experts"] if s.get("use_expert_bias", True) else 0
    return (attention_params(s) + norm_params(s) + s["num_experts"] * d
            + bias + s["num_experts"] * expert_params(s) + shared_params(s))


def parameters(s: dict) -> int:
    """Parameters held on this chip, from the sizes: the embedding and
    the untied head are two matrices."""
    d = s["hidden_size"]
    dense = len(s["layer_types"]) - expert_layers(s)
    return (2 * s["vocab_size"] * d + d + dense * dense_layer_params(s)
            + expert_layers(s) * expert_layer_params(s))


def expected_pairs(s: dict, length: int) -> float:
    """(token, expert) pairs a row routes, summed over the expert
    layers: every one of them lands here."""
    return float(expert_layers(s) * length * s["num_experts_per_tok"])


def parts_per_row(s: dict, length: int, pairs_per_row=None) -> dict:
    """The needed operations of one row by part, summed over the layers
    (``projections`` are q, k, v and o; the gate's is a part of its
    own). ``pairs_per_row`` is the count the program reports (summed
    over the expert layers); ``num_experts_per_tok`` a token where not
    given."""
    d = s["hidden_size"]
    if pairs_per_row is None:
        pairs_per_row = expected_pairs(s, length)
    kinds = list(s["layer_types"])
    dense = len(kinds) - expert_layers(s)
    return {
        "projections": 2.0 * length * len(kinds)
        * (attention_params(s) - gate_params(s) - 2 * head_dim(s)),
        "gate_projection": 2.0 * length * len(kinds) * gate_params(s),
        "causal_pairs": sum(flash_cost(s, k, 1, length)["flops"]
                            for k in kinds if k == "full_attention"),
        "banded_pairs": sum(flash_cost(s, k, 1, length)["flops"]
                            for k in kinds if k == "sliding_attention"),
        "dense": dense * gated_mlp_flops(d, s.get("intermediate_size", 0),
                                         length),
        "shared": expert_layers(s) * s.get("num_shared_experts", 0)
        * gated_mlp_flops(d, s["moe_intermediate_size"], length),
        "routed": gated_mlp_flops(d, s["moe_intermediate_size"],
                                  pairs_per_row),
        "router": 2.0 * length * d * s["num_experts"] * expert_layers(s),
        "head": 2.0 * d * s["vocab_size"]}


def forward_flops_per_row(s: dict, length: int, pairs_per_row=None
                          ) -> float:
    """One row through every layer and the head at its last position."""
    return sum(parts_per_row(s, length, pairs_per_row).values())
