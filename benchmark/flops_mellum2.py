"""Operations and bytes that the ``mellum2-12b-a2.5b-stage`` forward pass
needs, from the sizes of ``networkSpec`` alone: a row of l tokens
through grouped-query attention layers, "sliding_attention" ones (a
query sees its ``sliding_window`` newest keys, itself among them) and
"full_attention" ones (every earlier key), the routed experts after
each, and the untied head at the last position. Counts are of what the
mathematics requires: the banded pairs of a sliding layer and the causal
pairs of a full one, ``num_experts_per_tok`` experts a token (or the
pairs the program counted), K and V read once a key/value head, padded
rows are not work; element-wise work (norms, rotary, softmax of the
router) is not counted. Nothing here imports the program, so the count
is the same whatever implements it.
"""

from __future__ import annotations

ATTENTION = ("full_attention", "sliding_attention")


def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def banded_pairs(length: int, window: int) -> int:
    """(query, key) pairs with 0 <= p - j < window: query p sees
    min(p + 1, window) keys."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def pairs_of(s: dict, kind: str, length: int) -> int:
    return banded_pairs(length, s["sliding_window"]) \
        if kind == "sliding_attention" else causal_pairs(length)


def layers_of(s: dict, kind: str) -> int:
    return sum(1 for k in s["layer_types"] if k == kind)


def head_dim(s: dict) -> int:
    return s["head_dim"]


def attention_params(s: dict) -> int:
    """W_q and W_o d x H D, W_k and W_v d x Hkv D, the two head norms."""
    d, width = s["hidden_size"], head_dim(s)
    return (2 * d * s["num_attention_heads"] * width
            + 2 * d * s["num_key_value_heads"] * width + 2 * width)


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_params(s: dict) -> int:
    """An attention operator, the router, every expert, the two norms."""
    d = s["hidden_size"]
    return (attention_params(s) + 2 * d
            + s["num_experts"] * (d + expert_params(s)))


def parameters(s: dict) -> int:
    """Parameters held on this chip, from the sizes: the embedding and
    the untied head are two matrices."""
    d = s["hidden_size"]
    return 2 * s["vocab_size"] * d + d + len(s["layer_types"]) \
        * layer_params(s)


def gated_mlp_flops(hidden: int, width: int, tokens: float) -> float:
    return 2.0 * 3 * hidden * width * tokens


def flash_cost(s: dict, kind: str, batch: int, length: int,
               itemsize: int = 2) -> dict:
    """One grouped-query forward call of a ``kind`` layer over ``batch``
    rows: q.k and p.v for every pair the layer's mask leaves and every
    query head; reads q, and k and v once a key/value head, writes o and
    the float32 row sums."""
    h, hk, width = (s["num_attention_heads"], s["num_key_value_heads"],
                    head_dim(s))
    flops = 2.0 * 2 * batch * h * pairs_of(s, kind, length) * width
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
    """(token, expert) pairs a row routes, summed over the layers: every
    one of them lands here."""
    return float(len(s["layer_types"]) * length * s["num_experts_per_tok"])


def forward_flops_per_row(s: dict, length: int, pairs_per_row=None
                          ) -> float:
    """One row through every layer and the head at its last position.
    ``pairs_per_row`` is the count the program reports (summed over the
    layers); ``num_experts_per_tok`` a token where not given."""
    d = s["hidden_size"]
    if pairs_per_row is None:
        pairs_per_row = expected_pairs(s, length)
    total = 0.0
    for kind in s["layer_types"]:
        total += 2.0 * length * (attention_params(s) - 2 * head_dim(s))
        total += flash_cost(s, kind, 1, length)["flops"]
        total += 2.0 * length * d * s["num_experts"]             # router
    total += gated_mlp_flops(d, s["moe_intermediate_size"], pairs_per_row)
    return total + 2.0 * d * s["vocab_size"]                     # head
