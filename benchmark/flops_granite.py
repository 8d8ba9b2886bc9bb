"""Operations and bytes that the ``granite-4.0-h-micro`` forward pass
needs, from the sizes of ``networkSpec`` alone: a row of l tokens through
Mamba-2 layers (the two projections, the causal conv, the selective scan)
and grouped-query attention layers (four projections, the causal
pairs), a dense gated feed-forward in every layer, and the tied head at
the last position. Counts are of what the mathematics requires: the
scan's work is the chunked algorithm's at ``mamba_chunk_size`` with its
(T, T) products counted as the lower triangle they are, the chunks'
states carried once a chunk; padded rows and padded positions are not
work; element-wise work (norms, the gates, softplus, the decays) is not
counted. The scan's bytes are x, B and C read and y written in the
model's dtype, dt read in float32: once each. The causal pairs and the
flash call's cost are ``flops_mellum2``'s, which read the same keys.
Nothing here imports the program, so the count is the same whatever
implements it.
"""

from __future__ import annotations

from flops_mellum2 import (  # noqa: F401  (readers take them from here)
    flash_cost, gated_mlp_flops, head_dim, layers_of)


def mamba_sizes(s: dict) -> dict:
    inner = s["mamba_expand"] * s["hidden_size"]
    return {"inner": inner, "heads": s["mamba_n_heads"],
            "width": s["mamba_d_head"], "state": s["mamba_d_state"],
            "groups": s["mamba_n_groups"],
            "channels": inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]}


def mamba_projection_params(s: dict) -> int:
    """W_in d x (inner + conv channels + heads) and W_out inner x d."""
    n, d = mamba_sizes(s), s["hidden_size"]
    return d * (n["inner"] + n["channels"] + n["heads"]) + n["inner"] * d


def mamba_params(s: dict) -> int:
    """A Mamba-2 mixer: the projections, the conv's taps and bias,
    A_log, D and dt_bias a head, the gated norm's gain."""
    n = mamba_sizes(s)
    conv = (s["mamba_d_conv"] + 1) * n["channels"]
    return mamba_projection_params(s) + conv + 3 * n["heads"] + n["inner"]


def attention_projection_params(s: dict) -> int:
    """W_q and W_o d x H D, W_k and W_v d x Hkv D."""
    d, width = s["hidden_size"], head_dim(s)
    return 2 * d * width * (s["num_attention_heads"]
                            + s["num_key_value_heads"])


def attention_params(s: dict) -> int:
    """The projections and, where they are on, the two head norms."""
    norms = 2 * head_dim(s) if s.get("attention_qk_norm", True) else 0
    return attention_projection_params(s) + norms


def mlp_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["intermediate_size"]


def layer_params(s: dict, kind: str) -> int:
    """An operator, the dense feed-forward, the two norms."""
    op = mamba_params(s) if kind == "mamba" else attention_params(s)
    return op + mlp_params(s) + 2 * s["hidden_size"]


def parameters(s: dict) -> int:
    """Parameters held on this chip, from the sizes: a tied embedding,
    the final norm, every layer."""
    d = s["hidden_size"]
    head = 0 if s.get("tie_word_embeddings", True) else s["vocab_size"] * d
    return s["vocab_size"] * d + head + d + sum(
        layer_params(s, k) for k in s["layer_types"])


def chunk_pairs(s: dict, length: int) -> int:
    """(t, s) pairs with s <= t in the same chunk: the lower triangles
    of the chunks of a row, the last one as long as its real part."""
    chunk = s["mamba_chunk_size"]
    whole, rest = divmod(length, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def scan_cost(s: dict, rows: float, length: int) -> dict:
    """The selective scan of one Mamba-2 layer over ``rows`` rows of
    ``length``: within a chunk C.B^T a group and (L o C B^T) x a head over
    the lower triangle, each chunk's end state B^T x a head, the
    entering state to the output C S a head, and the carry of the
    state from one chunk to the next; x, B, C read and y written at 2
    bytes, dt read at 4."""
    n = mamba_sizes(s)
    state_work = 2.0 * n["heads"] * n["width"] * n["state"]
    pairs = chunk_pairs(s, length)
    chunks = -(-length // s["mamba_chunk_size"])
    flops = (2.0 * pairs * (n["groups"] * n["state"]
                            + n["heads"] * n["width"])
             + 2 * length * state_work + chunks * state_work)
    per_token = 2 * (2 * n["inner"] + 2 * n["groups"] * n["state"]) \
        + 4 * n["heads"]
    return {"flops": rows * flops, "bytes": rows * length * per_token}


def parts_per_row(s: dict, length: int) -> dict:
    """The needed operations of one row by part, summed over the
    layers."""
    d, kinds = s["hidden_size"], list(s["layer_types"])
    mamba = layers_of(s, "mamba")
    attention = len(kinds) - mamba
    channels = mamba_sizes(s)["channels"]
    return {
        "mlp": len(kinds) * gated_mlp_flops(d, s["intermediate_size"],
                                            length),
        "mamba_projections": 2.0 * length * mamba
        * mamba_projection_params(s),
        "scan": mamba * scan_cost(s, 1, length)["flops"],
        "conv": 2.0 * length * mamba * s["mamba_d_conv"] * channels,
        "attention_projections": 2.0 * length * attention
        * attention_projection_params(s),
        "causal_pairs": attention * flash_cost(
            s, "full_attention", 1, length)["flops"],
        "head": 2.0 * d * s["vocab_size"]}


def forward_flops_per_row(s: dict, length: int) -> float:
    """One row through every layer and the head at its last position."""
    return sum(parts_per_row(s, length).values())
