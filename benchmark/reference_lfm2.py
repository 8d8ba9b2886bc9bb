"""The plain reference of the ``lfm2-24b-a2b-stage`` configuration: a
decoder whose layers are gated short convolutions or grouped-query
attention, with a gated feed-forward or sigmoid-routed experts after
each; next-token logits of the last position. Plain ``jax.numpy`` in
float32, every product at highest precision, attention a dense masked
product in blocks of queries, the convolution three shifted multiplies,
the experts a loop over all of them with a mask; no kernels. Written
from the equations (ISSUE 34, docs/hybrid_moe_lm.md); it imports nothing
of the program and reads only a parameter tree and the sizes of
``networkSpec``:

    embed (V, d); embedding_norm (d,)
    layer_i_operator_norm, layer_i_ffn_norm (d,)
    layer_i_conv/{in_proj (d, 3d), conv (L, d), out_proj (d, d)}     "conv"
    layer_i_attn/{q_proj (d, H, D), k_proj, v_proj (d, Hkv, D),
        q_layernorm, k_layernorm (D,), out_proj (H, D, d)}  "full_attention"
    layer_i_mlp/{gate, up (d, w), down (w, d)}          i < num_dense_layers
    layer_i_moe/{router (E, d), router_bias (E,), experts_gate,
        experts_up (E, d, w), experts_down (E, w, d)}             the others

    h = x + Op(RMSNorm(x));  x' = h + FFN(RMSNorm(h))
    logits = E^T RMSNorm(x[last])              the embedding, tied
    conv: [B, C, z] = split3(u W_in); g = B * z;
      c[t] = w0 g[t-2] + w1 g[t-1] + w2 g[t] a channel, zeros before t = 0;
      Op = (C * c) W_out
    attention: q = u W_q, k = u W_k, v = u W_v; q, k <- RMSNorm over each
      head's D dims; q, k <- RoPE, pairs (i, i + D/2), theta; causal
      softmax(q k^T / sqrt(D)) v with key/value head h // (H / Hkv) for
      query head h; Op = o W_o
    experts: s = sigmoid(u . e_i) over all E; the k largest s + b chosen;
      g = scaling * s / (sum_chosen s + gate_norm_eps);
      FFN = sum_chosen g_i E_i(u), every E down(silu(gate u) * up u)

It goes layer by layer and row by row, the rows waiting on the host
between layers; an expert is raised to float32 as the loop reaches it,
so that it fits beside 10.4 GB of resident bfloat16 weights on one chip.

The controls are the same equations with one thing changed:
``matmul="fp8"`` rounds both operands of every matrix product to float8
e4m3 under one scale a tensor; ``routed=False`` leaves the routed
experts out; ``past_taps=False`` drops the convolution's two past taps
(w0 = w1 = 0); ``kv_head="mod"`` gives query head h the key/value head
h % Hkv; ``qk_norm=False`` leaves out the q and k norms;
``bias_in_choice=False`` chooses the experts by s alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # queries whose (H, block, l) scores are held at a
                        # time (a divisor of l, or l itself when shorter)
CONTROLS = {"matmul": "f32", "routed": True, "past_taps": True,
            "kv_head": "group", "qk_norm": True, "bias_in_choice": True}


def _fake_fp8(x):
    """Round to float8 e4m3 under one scale a tensor that puts the
    largest entry at 448, written as arithmetic."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    xs = x / scale
    exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(xs), 2.0 ** -6)))
    step = jnp.exp2(exp - 3)
    return jnp.clip(jnp.round(xs / step) * step, -448.0, 448.0) * scale


def _mm(expr, a, b, matmul):
    if matmul == "fp8":
        a, b = _fake_fp8(a), _fake_fp8(b)
    return jnp.einsum(expr, a, b, precision=_HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """The pairs (x[i], x[i + D/2]) of the last axis turned by
    t * theta**(-2i/D); x (l, heads, D), t the row."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def swiglu(u, gate, up, down, matmul):
    h = jax.nn.silu(_mm("tk,kn->tn", u, gate, matmul)) \
        * _mm("tk,kn->tn", u, up, matmul)
    return _mm("tn,nk->tk", h, down, matmul)


def conv_operator(p, u, matmul="f32", past_taps=True):
    """u (l, d) normed -> (l, d)."""
    length = u.shape[0]
    b, c, z = jnp.split(_mm("ld,de->le", u, p["in_proj"], matmul), 3, -1)
    g = b * z
    taps = p["conv"]
    n = taps.shape[0]
    out = taps[n - 1] * g
    if past_taps:
        for back in range(1, n):        # g[t - back], zeros before t = 0
            shifted = jnp.concatenate(
                [jnp.zeros((back, g.shape[1]), g.dtype), g[:length - back]])
            out = out + taps[n - 1 - back] * shifted
    return _mm("ld,de->le", c * out, p["out_proj"], matmul)


def attention_operator(p, spec, u, matmul="f32", kv_head="group",
                       qk_norm=True):
    """u (l, d) normed -> (l, d)."""
    length = u.shape[0]
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    q = _mm("ld,dhk->lhk", u, p["q_proj"], matmul)
    k = _mm("ld,dhk->lhk", u, p["k_proj"], matmul)
    v = _mm("ld,dhk->lhk", u, p["v_proj"], matmul)
    if qk_norm:
        q = rms_norm(q, p["q_layernorm"], spec["norm_eps"])
        k = rms_norm(k, p["k_layernorm"], spec["norm_eps"])
    q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    serves = np.arange(heads) // (heads // kv) if kv_head == "group" \
        else np.arange(heads) % kv
    k, v = k[:, serves], v[:, serves]           # (l, H, D), plainly repeated
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    scale = np.float32(q.shape[-1]) ** -0.5

    def attend(args):                   # one block of queries, every key
        q_b, first = args
        s = _mm("qhd,khd->hqk", q_b, k, matmul) * scale
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(length)
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", prob, v, matmul)
    o = jax.lax.map(attend, (q.reshape(length // block, block, heads, -1),
                             jnp.arange(0, length, block)))
    return _mm("lhk,hkd->ld", o.reshape(length, heads, -1), p["out_proj"],
               matmul)


def experts(p, spec, u, matmul="f32", routed=True, bias_in_choice=True,
            forced=None):
    """u (t, d). Returns (y (t, d), chosen (t, k), margin (t,), gap,
    miss): the margin is how far score + bias would have to move for the
    chosen k to change, the k-th largest less the next. ``forced`` (T, k)
    puts another's choice in the place of this layer's own at the last T
    positions (the experts a program chose there, so that what is
    compared downstream is the arithmetic and not a near tie's coin);
    ``gap`` (T,) is then how far under the k-th largest score + bias the
    lowest of the forced experts lies: 0 where the choice is this
    layer's own, a rounding's worth at a near tie, more for a choice by
    another rule; ``miss`` (T,) counts the forced experts that are not
    among this layer's own k."""
    k = spec["num_experts_per_tok"]
    logits = jnp.einsum("td,ed->te", u, p["router"].astype(jnp.float32),
                        precision=_HI)
    score = jax.nn.sigmoid(logits)
    biased = score + p["router_bias"] if bias_in_choice else score
    order = jnp.argsort(-biased, axis=-1, stable=True)
    chosen = order[:, :k]
    ranked = jnp.take_along_axis(biased, order[:, :k + 1], axis=-1)
    margin = ranked[:, k - 1] - ranked[:, k]
    gap = miss = None
    if forced is not None:
        tail = forced.shape[0]
        theirs = jnp.take_along_axis(biased[-tail:], forced, axis=-1)
        gap = jnp.maximum(ranked[-tail:, k - 1] - theirs.min(-1), 0.0)
        own = chosen[-tail:]
        miss = k - (forced[:, :, None] == own[:, None, :]).any(-1).sum(-1)
        chosen = chosen.at[-tail:].set(forced)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    gate = spec["routed_scaling_factor"] * picked \
        / (picked.sum(-1, keepdims=True) + spec["gate_norm_eps"])
    y = jnp.zeros_like(u)
    if not routed:
        return y, chosen, margin, gap, miss

    def add_expert(y, expert):
        # every token through expert e, weighted by its gate (0 where
        # the token did not choose it): dense, and plainly the sum; the
        # expert's weights are raised to float32 here, one at a time
        e, w_gate, w_up, w_down = expert
        g = jnp.sum(jnp.where(chosen == e, gate, 0.0), -1)
        return y + g[:, None] * swiglu(
            u, w_gate.astype(jnp.float32), w_up.astype(jnp.float32),
            w_down.astype(jnp.float32), matmul), None
    y, _ = jax.lax.scan(add_expert, y, (
        jnp.arange(p["experts_gate"].shape[0]), p["experts_gate"],
        p["experts_up"], p["experts_down"]))
    return y, chosen, margin, gap, miss


def cone(spec, layer: int) -> int:
    """How many of a row's last positions can reach the last one's
    logits from ``layer``'s feed-forward by the convolutions alone: each
    "conv" layer after it carries a position's state two positions on
    (taps at t-2, t-1, t). Every earlier position reaches the last
    through attention only, one key among l; and a position of the cone
    is itself reached by convolutions only from the cones of the layers
    before it."""
    later = sum(1 for kind in spec["layer_types"][layer + 1:]
                if kind == "conv")
    return 1 + (spec["conv_L_cache"] - 1) * later


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layer(params, i):
    return {k[len(f"layer_{i}_"):]: v for k, v in params.items()
            if k.startswith(f"layer_{i}_")}


@functools.partial(jax.jit, static_argnames=("spec", "controls"))
def _layer_row(p, x, forced=None, *, spec, controls):
    """One row through one layer -> (x', the operator's output, and for
    an expert layer (chosen, margin, gap, miss))."""
    spec, c = dict(spec), dict(controls)
    eps, mm = spec["norm_eps"], c["matmul"]
    moe = p.pop("moe", None)     # stays as held; an expert at a time
    p = _f32(p)
    u = rms_norm(x, p["operator_norm"], eps)
    if "conv" in p:
        a = conv_operator(p["conv"], u, mm, c["past_taps"])
    else:
        a = attention_operator(p["attn"], spec, u, mm, c["kv_head"],
                               c["qk_norm"])
    x = x + a
    u = rms_norm(x, p["ffn_norm"], eps)
    if moe is None:
        m = p["mlp"]
        return x + swiglu(u, m["gate"], m["up"], m["down"], mm), a, None
    y, chosen, margin, gap, miss = experts(moe, spec, u, mm, c["routed"],
                                           c["bias_in_choice"], forced)
    if forced is None:
        gap, miss = jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32)
    return x + y, a, (chosen, margin, gap, miss)


def forward(params, tokens, spec, keep_blocks=False, forced_tail=None,
            **controls) -> dict:
    """tokens (n, l) ids. Returns {"logits": (n, V) float32, "routed":
    {layer: (n, l, k)}, "router_margin": {layer: (n, l)}} as numpy
    arrays, the last two for the expert layers; with ``keep_blocks``
    (True, or the layers to keep) also "blocks" and "operators" {layer:
    (n, l, d)}: the hidden state after each layer and each operator's
    output. ``forced_tail`` (n, expert layers, T, k) are the experts a
    program chose at each row's last T positions, in the expert layers'
    order: at the positions of a layer's ``cone`` (its last ``cone``
    of the T, and those alone) they take the place of the reference's
    own choice (``experts``), and "route_gap" and "route_miss" {layer:
    (n, cone)} say of every forced position how far from the
    reference's own the choice was and how many of its k experts the
    reference did not choose: nothing is taken over unchecked. Before
    the cone the reference keeps its own choices: those positions reach
    the last one through attention alone, and their inputs a near tie
    further back may have moved. ``controls`` are the stand-ins of the
    module's docstring."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise TypeError(f"unknown controls {sorted(unknown)}")
    controls = tuple(sorted({**CONTROLS, **controls}.items()))
    tokens = np.asarray(tokens)
    sizes = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)))
    mm = dict(controls)["matmul"]
    kept = range(len(spec["layer_types"])) if keep_blocks is True \
        else tuple(keep_blocks or ())
    with jax.default_matmul_precision("highest"):
        # rows wait on the host between layers: the chip holds the
        # resident weights, one row and one layer's temporaries
        xs = [np.asarray(jnp.asarray(params["embed"])[jnp.asarray(row)]
                         .astype(jnp.float32)) for row in tokens]
        chosen_by, margin_by, gap_by, miss_by = {}, {}, {}, {}
        blocks, operators = {}, {}
        for i in range(len(spec["layer_types"])):
            p = _layer(params, i)
            routed_rows, ops = [], []
            nth = i - spec["num_dense_layers"]      # among the expert layers
            for r, x in enumerate(xs):
                forced = None if forced_tail is None or nth < 0 \
                    else jnp.asarray(forced_tail[r][nth][-cone(spec, i):],
                                     jnp.int32)
                x, a, routed_row = _layer_row(dict(p), x, forced,
                                              spec=sizes, controls=controls)
                xs[r] = np.asarray(x)
                routed_rows.append(routed_row)
                if i in kept:
                    ops.append(np.asarray(a))
            if i in kept:
                blocks[i], operators[i] = np.stack(xs), np.stack(ops)
            if routed_rows[0] is not None:
                chosen_by[i], margin_by[i], gap_by[i], miss_by[i] = (
                    np.stack([np.asarray(row[j]) for row in routed_rows])
                    for j in range(4))
            del p
        last = jnp.asarray(np.stack([x[-1] for x in xs]))
        last = rms_norm(last, jnp.asarray(params["embedding_norm"],
                                          jnp.float32), spec["norm_eps"])
        logits = _mm("bd,vd->bv", last,
                     jnp.asarray(params["embed"], jnp.float32), mm)
    out = {"logits": np.asarray(logits), "routed": chosen_by,
           "router_margin": margin_by, "route_gap": gap_by,
           "route_miss": miss_by}
    if kept:
        out.update(blocks=blocks, operators=operators)
    return out
