"""The plain reference of the ``trinity-mini-stage`` configuration: a
decoder whose layers are grouped-query attention with an output gate,
three with a sliding window of ``sliding_window`` keys to every full
one, rotary positions on the sliding layers alone, four norms a layer,
the first ``num_dense_layers`` layers with a gated feed-forward and the
others with sigmoid-routed experts and a shared one beside them; a
scaled embedding, an untied head; next-token logits of the last
position. Plain ``jax.numpy`` in float32, every product at highest
precision, attention a dense masked product in blocks of queries with
the mask built from positions, the experts a loop over all of them with
a mask; no kernels. Written from the equations (ISSUE 40,
docs/hybrid_moe_lm.md); it imports nothing of the program and reads only
a parameter tree and the sizes of ``networkSpec``:

    embed, lm_head (V, d); embedding_norm (d,)
    layer_i_operator_norm, layer_i_operator_post_norm,
    layer_i_ffn_norm, layer_i_ffn_post_norm (d,)
    layer_i_attn/{q_proj, gate_proj (d, H, D), k_proj, v_proj (d, Hkv, D),
        q_layernorm, k_layernorm (D,), out_proj (H, D, d)}
    layer_i_mlp/{gate, up (d, f), down (f, d)}          i < num_dense_layers
    layer_i_moe/{router (E, d), router_bias (E,), experts_gate,
        experts_up (E, d, w), experts_down (E, w, d),
        shared_j/{gate, up (d, w), down (w, d)}}        the others

    x0 = sqrt(d) embed[tokens]
    h  = x + N(Attn(N(x; operator_norm)); operator_post_norm)
    x' = h + N(FFN(N(h; ffn_norm)); ffn_post_norm)
    logits = lm_head N(x[last]; embedding_norm)
    attention: q = u W_q, k = u W_k, v = u W_v, g = u W_g; q, k <- RMSNorm
      over each head's D dims; on a layer whose kind has a rotary table
      q, k <- RoPE, pairs (i, i + D/2), inv_i = theta^(-2i/D), and on a
      kind whose rope_type is "none" no rotary step; key/value head
      h // (H / Hkv) serves query head h; query p sees key j where
      j <= p, and on a "sliding_attention" layer only where
      p - j < sliding_window; o = softmax(q k^T / sqrt(D)) v;
      Attn = (o * sigmoid(g)) W_o
    experts: s = sigmoid(u . e_i) over all E; the k largest s + b chosen;
      w = scale * s / (sum_chosen s + eps); MoE = sum_j S_j(u)
      + sum_chosen w_i E_i(u), every E and S down(silu(gate u) * up u)

It goes layer by layer and row by row, the rows waiting on the host
between layers; an expert is raised to float32 as the loop reaches it,
so that it fits beside 8.5 GB of resident bfloat16 weights on one chip.

The controls are the same equations with one thing changed (``CONTROLS``
holds the sound values): ``matmul="fp8"`` rounds both operands of every
matrix product to float8 e4m3 under one scale a tensor; ``routed=False``
and ``shared=False`` leave the routed or the shared experts out;
``gate=False`` takes sigmoid(g) as 1 and ``gate_input="raw"`` takes the
gate of the un-normed x in u's place; ``full_rope=True`` turns the full
layers by the default table and ``sliding_rope=False`` leaves the
sliding layers unturned; ``window=None`` lets the sliding layers see
every earlier key and ``window=<n>`` gives them another window (2047:
one key short); ``post_norms=False`` adds the branches un-normed;
``embed_scale=False`` leaves the embedding as held;
``route_scale=False`` takes ``routed_scaling_factor`` as 1;
``renormalise=False`` leaves the chosen scores undivided;
``scoring="softmax"`` scores by a softmax over every expert;
``bias_in_choice=False`` chooses by the scores alone; ``kv_head="mod"``
gives query head h the key/value head h % Hkv.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # queries whose (H, block, l) scores are held at a
                        # time (a divisor of l, or l itself when shorter)
CONTROLS = {"matmul": "f32", "routed": True, "shared": True, "gate": True,
            "gate_input": "normed", "full_rope": False,
            "sliding_rope": True, "window": "spec", "post_norms": True,
            "embed_scale": True, "route_scale": True, "renormalise": True,
            "scoring": "sigmoid", "bias_in_choice": True,
            "kv_head": "group"}
ATTENTION = ("full_attention", "sliding_attention")


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


def rope_table(spec, kind: str, width: int):
    """The inverse frequencies (D/2,) float64 of an attention kind's
    layers from ``rope_parameters[kind]``, or None where its rope_type
    is "none": that kind takes no rotary step."""
    table = {"rope_type": "default", "rope_theta": spec.get("rope_theta"),
             **(spec.get("rope_parameters") or {}).get(kind, {})}
    if table["rope_type"] == "none":
        return None
    if table["rope_type"] != "default":
        raise ValueError(f"this reference knows the default table and "
                         f"none, not {table['rope_type']!r}")
    return table["rope_theta"] ** (
        -np.arange(0, width, 2, dtype=np.float64) / width)


def rope(x, inv):
    """The pairs (x[i], x[i + D/2]) of the last axis turned by t * inv_i;
    x (l, heads, D), t the row."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu(u, gate, up, down, matmul):
    h = jax.nn.silu(_mm("tk,kn->tn", u, gate, matmul)) \
        * _mm("tk,kn->tn", u, up, matmul)
    return _mm("tn,nk->tk", h, down, matmul)


def attention_operator(p, spec, kind, u, x=None, matmul="f32", gate=True,
                       gate_input="normed", full_rope=False,
                       sliding_rope=True, window="spec", kv_head="group"):
    """u (l, d) normed (and x, the same un-normed, which only the
    ``gate_input="raw"`` control reads) -> (l, d), a layer of ``kind``."""
    length = u.shape[0]
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    q = _mm("ld,dhk->lhk", u, p["q_proj"], matmul)
    k = _mm("ld,dhk->lhk", u, p["k_proj"], matmul)
    v = _mm("ld,dhk->lhk", u, p["v_proj"], matmul)
    q = rms_norm(q, p["q_layernorm"], spec["norm_eps"])
    k = rms_norm(k, p["k_layernorm"], spec["norm_eps"])
    inv = rope_table(spec, kind, q.shape[-1])
    if kind == "full_attention" and full_rope:          # a control
        inv = rope_table({"rope_theta": spec["rope_theta"]}, kind,
                         q.shape[-1])
    if kind == "sliding_attention" and not sliding_rope:    # a control
        inv = None
    if inv is not None:
        q, k = rope(q, inv), rope(k, inv)
    serves = np.arange(heads) // (heads // kv) if kv_head == "group" \
        else np.arange(heads) % kv
    k, v = k[:, serves], v[:, serves]           # (l, H, D), plainly repeated
    if window == "spec":
        window = spec.get("sliding_window")
    if kind != "sliding_attention":
        window = None
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    scale = np.float32(q.shape[-1]) ** -0.5

    def attend(args):                   # one block of queries, every key
        q_b, first = args
        s = _mm("qhd,khd->hqk", q_b, k, matmul) * scale
        ago = (first + jnp.arange(block))[:, None] - jnp.arange(length)
        seen = ago >= 0                 # key j <= query p ...
        if window is not None:
            seen &= ago < window        # ... and p - j < window
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", prob, v, matmul)
    o = jax.lax.map(attend, (q.reshape(length // block, block, heads, -1),
                             jnp.arange(0, length, block)))
    o = o.reshape(length, heads, -1)
    if spec.get("attention_output_gate") and gate:
        g = _mm("ld,dhk->lhk", u if gate_input == "normed" else x,
                p["gate_proj"], matmul)
        o = o * jax.nn.sigmoid(g)       # before W_o, element by element
    return _mm("lhk,hkd->ld", o, p["out_proj"], matmul)


def experts(p, spec, u, matmul="f32", routed=True, shared=True,
            route_scale=True, renormalise=True, scoring="sigmoid",
            bias_in_choice=True, forced=None):
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
    score = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = score + p["router_bias"] \
        if bias_in_choice and "router_bias" in p else score
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
    weight = jnp.take_along_axis(score, chosen, axis=-1)  # without the bias
    if renormalise:
        weight = weight / (weight.sum(-1, keepdims=True)
                           + spec["gate_norm_eps"])
    if route_scale:
        weight = spec["routed_scaling_factor"] * weight
    y = jnp.zeros_like(u)
    if shared:                          # added unweighted
        for j in range(spec.get("num_shared_experts", 0)):
            s = _f32(p[f"shared_{j}"])
            y = y + swiglu(u, s["gate"], s["up"], s["down"], matmul)
    if not routed:
        return y, chosen, margin, gap, miss

    def add_expert(y, expert):
        # every token through expert e, weighted by its gate (0 where
        # the token did not choose it): dense, and plainly the sum; the
        # expert's weights are raised to float32 here, one at a time
        e, w_gate, w_up, w_down = expert
        g = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        return y + g[:, None] * swiglu(
            u, w_gate.astype(jnp.float32), w_up.astype(jnp.float32),
            w_down.astype(jnp.float32), matmul), None
    y, _ = jax.lax.scan(add_expert, y, (
        jnp.arange(p["experts_gate"].shape[0]), p["experts_gate"],
        p["experts_up"], p["experts_down"]))
    return y, chosen, margin, gap, miss


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layer(params, i):
    return {k[len(f"layer_{i}_"):]: v for k, v in params.items()
            if k.startswith(f"layer_{i}_")}


@functools.partial(jax.jit, static_argnames=("kind", "spec", "controls"))
def _layer_row(p, x, forced=None, *, kind, spec, controls):
    """One row through one layer -> (x', the operator's output, and of
    an expert layer (chosen, margin, gap, miss): empty arrays of a dense
    one)."""
    spec, c = json.loads(spec), dict(controls)     # hashable for the jit
    eps, mm = spec["norm_eps"], c["matmul"]
    moe = p.pop("moe", None)    # stays as held; an expert at a time
    p = _f32(p)
    sandwich = spec.get("sandwich_norms") and c["post_norms"]

    def post(y, gain):          # a branch's output, normed before the add
        return rms_norm(y, p[gain], eps) if sandwich else y
    u = rms_norm(x, p["operator_norm"], eps)
    a = attention_operator(
        p["attn"], spec, kind, u, x, mm, c["gate"], c["gate_input"],
        c["full_rope"], c["sliding_rope"], c["window"], c["kv_head"])
    x = x + post(a, "operator_post_norm")
    u = rms_norm(x, p["ffn_norm"], eps)
    none = jnp.zeros((0,), jnp.float32)
    if moe is None:
        m = p["mlp"]
        y, routed = swiglu(u, m["gate"], m["up"], m["down"], mm), (
            jnp.zeros((u.shape[0], 0), jnp.int32), none, none, none)
    else:
        y, chosen, margin, gap, miss = experts(
            moe, spec, u, mm, c["routed"], c["shared"], c["route_scale"],
            c["renormalise"], c["scoring"], c["bias_in_choice"], forced)
        if forced is None:
            gap, miss = none, jnp.zeros((0,), jnp.int32)
        routed = (chosen, margin, gap, miss)
    return x + post(y, "ffn_post_norm"), a, routed


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(last, gain, head, *, eps, matmul):
    """logits = head N(x[last]; gain). One program, so that the head,
    raised to float32 (1.6 GB at 200192 x 2048), and the fp8 control's
    rounding of it are not so many arrays of that size at once."""
    last = rms_norm(last, gain.astype(jnp.float32), eps)
    return _mm("bd,vd->bv", last, head.astype(jnp.float32), matmul)


def forward(params, tokens, spec, keep_blocks=False, keep_tail=None,
            forced_tail=None, **controls) -> dict:
    """tokens (n, l) ids. Returns {"logits": (n, V) float32, "routed":
    {layer: (n, l, k)}, "router_margin": {layer: (n, l)}} as numpy
    arrays, the expert layers alone in those; with ``keep_blocks``
    (True, or the layers to keep) also "blocks" and "operators" {layer:
    (n, l, d)}: the hidden state after each layer and each operator's
    output (before its post norm), their last ``keep_tail`` positions
    alone where that is given. ``forced_tail`` (n, expert layers, T, k)
    are the experts a program chose at each row's last T positions: at
    the **last position** of every expert layer (and there alone) they
    take the place of the reference's own choice (``experts``), and
    "route_gap" and "route_miss" {layer: (n, 1)} say of every forced
    position how far from the reference's own the choice was and how
    many of its k experts the reference did not choose: nothing is taken
    over unchecked. No operator of this family carries a position's
    state sideways but attention, so every earlier position reaches the
    last one as one key among many, and the reference keeps its own
    choices there. ``controls`` are the stand-ins of the module's
    docstring."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise TypeError(f"unknown controls {sorted(unknown)}")
    controls = {**CONTROLS, **controls}
    tokens = np.asarray(tokens)
    kinds = list(spec["layer_types"])
    if set(kinds) - set(ATTENTION):
        raise ValueError("this reference knows attention layers, with a "
                         "dense or an expert feed-forward, and no other")
    dense = spec.get("num_dense_layers", 0)
    sizes = json.dumps({k: v for k, v in spec.items()
                        if k not in ("layer_types", "type", "dtype")},
                       sort_keys=True)
    mm = controls["matmul"]
    kept = range(len(kinds)) if keep_blocks is True \
        else tuple(keep_blocks or ())
    tail = slice(None) if keep_tail is None else slice(-keep_tail, None)
    scale = float(np.sqrt(spec["hidden_size"])) \
        if spec.get("mup_enabled") and controls["embed_scale"] else 1.0
    controls = tuple(sorted(controls.items()))
    with jax.default_matmul_precision("highest"):
        # rows wait on the host between layers: the chip holds the
        # resident weights, one row and one layer's temporaries
        xs = [np.asarray(jnp.asarray(params["embed"])[jnp.asarray(row)]
                         .astype(jnp.float32) * scale) for row in tokens]
        chosen_by, margin_by, gap_by, miss_by = {}, {}, {}, {}
        blocks, operators = {}, {}
        for i, kind in enumerate(kinds):
            p = _layer(params, i)
            routed_rows, ops = [], []
            for r, x in enumerate(xs):
                forced = None if forced_tail is None or i < dense \
                    else jnp.asarray(forced_tail[r][i - dense][-1:],
                                     jnp.int32)
                x, a, routed_row = _layer_row(
                    dict(p), x, forced, kind=kind, spec=sizes,
                    controls=controls)
                xs[r] = np.asarray(x)
                routed_rows.append(routed_row)
                if i in kept:
                    ops.append(np.asarray(a[tail]))
            if i in kept:
                blocks[i] = np.stack([x[tail] for x in xs])
                operators[i] = np.stack(ops)
            if i >= dense:
                chosen_by[i], margin_by[i], gap_by[i], miss_by[i] = (
                    np.stack([np.asarray(row[j]) for row in routed_rows])
                    for j in range(4))
            del p
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"]
        logits = _head(jnp.asarray(np.stack([x[-1] for x in xs])),
                       params["embedding_norm"], head,
                       eps=spec["norm_eps"], matmul=mm)
    out = {"logits": np.asarray(logits), "routed": chosen_by,
           "router_margin": margin_by, "route_gap": gap_by,
           "route_miss": miss_by}
    if kept:
        out.update(blocks=blocks, operators=operators)
    return out
