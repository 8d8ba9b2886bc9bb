"""The plain reference of the ``mellum2-12b-a2.5b-stage`` configuration:
a decoder whose layers are grouped-query attention, three with a sliding
window of ``sliding_window`` keys to every full one, each followed by
softmax-routed experts; an untied head; next-token logits of the last
position. Plain ``jax.numpy`` in float32, every product at highest
precision, attention a dense masked product in blocks of queries with
the mask built from positions, the two rotary tables from their
formulas, the experts a loop over all of them with a mask; no kernels.
Written from the equations (ISSUE 36, docs/hybrid_moe_lm.md); it imports
nothing of the program and reads only a parameter tree and the sizes of
``networkSpec``:

    embed, lm_head (V, d); embedding_norm (d,)
    layer_i_operator_norm, layer_i_ffn_norm (d,)
    layer_i_attn/{q_proj (d, H, D), k_proj, v_proj (d, Hkv, D),
        q_layernorm, k_layernorm (D,), out_proj (H, D, d)}
    layer_i_moe/{router (E, d), experts_gate, experts_up (E, d, w),
        experts_down (E, w, d)}

    h = x + Attn(RMSNorm(x));  x' = h + MoE(RMSNorm(h))
    logits = lm_head RMSNorm(x[last])
    attention: q = u W_q, k = u W_k, v = u W_v; q, k <- RMSNorm over each
      head's D dims; q, k <- RoPE of the layer's kind, pairs (i, i + D/2);
      key/value head h // (H / Hkv) serves query head h; query p sees key
      j where j <= p, and on a "sliding_attention" layer only where
      p - j < sliding_window; softmax(q k^T / sqrt(D)) v; Attn = o W_o
    RoPE "default": inv_i = theta^(-2i/D); angle = p inv_i
    RoPE "yarn": low = floor(D ln(orig / (beta_fast 2 pi)) / (2 ln theta)),
      high = ceil(D ln(orig / (beta_slow 2 pi)) / (2 ln theta)),
      ramp_i = clip((i - low) / (high - low), 0, 1),
      inv_i = (1 - ramp_i) theta^(-2i/D) + ramp_i theta^(-2i/D) / factor;
      cos and sin both times attention_factor
    experts: s = softmax(u . e_i) over all E; the k largest s chosen;
      g = s / sum_chosen s; MoE = sum_chosen g_i E_i(u), every E
      down(silu(gate u) * up u)

It goes layer by layer and row by row, the rows waiting on the host
between layers; an expert is raised to float32 as the loop reaches it,
so that it fits beside 7.6 GB of resident bfloat16 weights on one chip.

The controls are the same equations with one thing changed:
``matmul="fp8"`` rounds both operands of every matrix product to float8
e4m3 under one scale a tensor; ``routed=False`` leaves the routed
experts out; ``window=None`` lets the sliding layers see every earlier
key and ``window=<n>`` gives them another window (1023: one key short);
``yarn=False`` turns the full layers by the default table;
``attention_factor=False`` leaves YaRN's factor off cos and sin;
``kv_head="mod"`` gives query head h the key/value head h % Hkv;
``scoring="sigmoid"`` scores each expert by its logit's sigmoid;
``renormalise=False`` leaves the chosen scores as they are.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # queries whose (H, block, l) scores are held at a
                        # time (a divisor of l, or l itself when shorter)
CONTROLS = {"matmul": "f32", "routed": True, "window": "spec",
            "yarn": True, "attention_factor": True, "kv_head": "group",
            "scoring": "softmax", "renormalise": True}
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


def rope_table(spec, kind: str, width: int, yarn=True,
               attention_factor=True):
    """(inverse frequencies (D/2,) float64, factor on cos and sin) of an
    attention kind's layers, from ``rope_parameters[kind]``."""
    table = {"rope_type": "default", "rope_theta": spec.get("rope_theta"),
             **(spec.get("rope_parameters") or {}).get(kind, {})}
    theta = table["rope_theta"]
    base = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    if table["rope_type"] != "yarn" or not yarn:
        return base, 1.0
    original = table["original_max_position_embeddings"]

    def turns(beta):    # the pair that turns beta times over `original`
        return width * math.log(original / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns(table["beta_fast"])), 0)
    high = min(math.ceil(turns(table["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 - ramp) * base + ramp * base / table["factor"]
    return inv, (table["attention_factor"] if attention_factor else 1.0)


def rope(x, inv, factor):
    """The pairs (x[i], x[i + D/2]) of the last axis turned by t * inv_i,
    cos and sin times ``factor``; x (l, heads, D), t the row."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu(u, gate, up, down, matmul):
    h = jax.nn.silu(_mm("tk,kn->tn", u, gate, matmul)) \
        * _mm("tk,kn->tn", u, up, matmul)
    return _mm("tn,nk->tk", h, down, matmul)


def attention_operator(p, spec, kind, u, matmul="f32", window="spec",
                       yarn=True, attention_factor=True, kv_head="group"):
    """u (l, d) normed -> (l, d), a layer of ``kind``."""
    length = u.shape[0]
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    q = _mm("ld,dhk->lhk", u, p["q_proj"], matmul)
    k = _mm("ld,dhk->lhk", u, p["k_proj"], matmul)
    v = _mm("ld,dhk->lhk", u, p["v_proj"], matmul)
    q = rms_norm(q, p["q_layernorm"], spec["norm_eps"])
    k = rms_norm(k, p["k_layernorm"], spec["norm_eps"])
    inv, factor = rope_table(spec, kind, q.shape[-1], yarn,
                             attention_factor)
    q, k = rope(q, inv, factor), rope(k, inv, factor)
    serves = np.arange(heads) // (heads // kv) if kv_head == "group" \
        else np.arange(heads) % kv
    k, v = k[:, serves], v[:, serves]           # (l, H, D), plainly repeated
    if window == "spec":
        window = spec["sliding_window"]
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
    return _mm("lhk,hkd->ld", o.reshape(length, heads, -1), p["out_proj"],
               matmul)


def experts(p, spec, u, matmul="f32", routed=True, scoring="softmax",
            renormalise=True, forced=None):
    """u (t, d). Returns (y (t, d), chosen (t, k), margin (t,), gap,
    miss): the margin is how far a score would have to move for the
    chosen k to change, the k-th largest less the next. ``forced`` (T, k)
    puts another's choice in the place of this layer's own at the last T
    positions (the experts a program chose there, so that what is
    compared downstream is the arithmetic and not a near tie's coin);
    ``gap`` (T,) is then how far under the k-th largest score the lowest
    of the forced experts lies: 0 where the choice is this layer's own,
    a rounding's worth at a near tie, more for a choice by another rule;
    ``miss`` (T,) counts the forced experts that are not among this
    layer's own k."""
    k = spec["num_experts_per_tok"]
    logits = jnp.einsum("td,ed->te", u, p["router"].astype(jnp.float32),
                        precision=_HI)
    score = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    order = jnp.argsort(-score, axis=-1, stable=True)
    chosen = order[:, :k]
    ranked = jnp.take_along_axis(score, order[:, :k + 1], axis=-1)
    margin = ranked[:, k - 1] - ranked[:, k]
    gap = miss = None
    if forced is not None:
        tail = forced.shape[0]
        theirs = jnp.take_along_axis(score[-tail:], forced, axis=-1)
        gap = jnp.maximum(ranked[-tail:, k - 1] - theirs.min(-1), 0.0)
        own = chosen[-tail:]
        miss = k - (forced[:, :, None] == own[:, None, :]).any(-1).sum(-1)
        chosen = chosen.at[-tail:].set(forced)
    gate = jnp.take_along_axis(score, chosen, axis=-1)
    if renormalise:
        gate = gate / gate.sum(-1, keepdims=True)
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


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layer(params, i):
    return {k[len(f"layer_{i}_"):]: v for k, v in params.items()
            if k.startswith(f"layer_{i}_")}


@functools.partial(jax.jit, static_argnames=("kind", "spec", "controls"))
def _layer_row(p, x, forced=None, *, kind, spec, controls):
    """One row through one layer -> (x', the operator's output,
    (chosen, margin, gap, miss))."""
    spec, c = json.loads(spec), dict(controls)     # hashable for the jit
    eps, mm = spec["norm_eps"], c["matmul"]
    moe = p.pop("moe")          # stays as held; an expert at a time
    p = _f32(p)
    u = rms_norm(x, p["operator_norm"], eps)
    a = attention_operator(p["attn"], spec, kind, u, mm, c["window"],
                           c["yarn"], c["attention_factor"], c["kv_head"])
    x = x + a
    u = rms_norm(x, p["ffn_norm"], eps)
    y, chosen, margin, gap, miss = experts(
        moe, spec, u, mm, c["routed"], c["scoring"], c["renormalise"],
        forced)
    if forced is None:
        gap, miss = jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32)
    return x + y, a, (chosen, margin, gap, miss)


def forward(params, tokens, spec, keep_blocks=False, keep_tail=None,
            forced_tail=None, **controls) -> dict:
    """tokens (n, l) ids. Returns {"logits": (n, V) float32, "routed":
    {layer: (n, l, k)}, "router_margin": {layer: (n, l)}} as numpy
    arrays; with ``keep_blocks`` (True, or the layers to keep) also
    "blocks" and "operators" {layer: (n, l, d)}: the hidden state after
    each layer and each operator's output, their last ``keep_tail``
    positions alone where that is given. ``forced_tail`` (n, layers, T,
    k) are the experts a program chose at each row's last T positions:
    at the **last position** of every layer (and there alone) they take
    the place of the reference's own choice (``experts``), and
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
    controls = tuple(sorted({**CONTROLS, **controls}.items()))
    tokens = np.asarray(tokens)
    kinds = list(spec["layer_types"])
    if set(kinds) - set(ATTENTION) or spec.get("num_dense_layers", 0):
        raise ValueError("this reference knows attention layers with "
                         "routed experts after each, and no other")
    sizes = json.dumps({k: v for k, v in spec.items()
                        if k not in ("layer_types", "type", "dtype")},
                       sort_keys=True)
    mm = dict(controls)["matmul"]
    kept = range(len(kinds)) if keep_blocks is True \
        else tuple(keep_blocks or ())
    tail = slice(None) if keep_tail is None else slice(-keep_tail, None)
    with jax.default_matmul_precision("highest"):
        # rows wait on the host between layers: the chip holds the
        # resident weights, one row and one layer's temporaries
        xs = [np.asarray(jnp.asarray(params["embed"])[jnp.asarray(row)]
                         .astype(jnp.float32)) for row in tokens]
        chosen_by, margin_by, gap_by, miss_by = {}, {}, {}, {}
        blocks, operators = {}, {}
        for i, kind in enumerate(kinds):
            p = _layer(params, i)
            routed_rows, ops = [], []
            for r, x in enumerate(xs):
                forced = None if forced_tail is None else jnp.asarray(
                    forced_tail[r][i][-1:], jnp.int32)
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
            chosen_by[i], margin_by[i], gap_by[i], miss_by[i] = (
                np.stack([np.asarray(row[j]) for row in routed_rows])
                for j in range(4))
            del p
        last = jnp.asarray(np.stack([x[-1] for x in xs]))
        last = rms_norm(last, jnp.asarray(params["embedding_norm"],
                                          jnp.float32), spec["norm_eps"])
        logits = _mm("bd,vd->bv", last,
                     jnp.asarray(params["lm_head"], jnp.float32), mm)
    out = {"logits": np.asarray(logits), "routed": chosen_by,
           "router_margin": margin_by, "route_gap": gap_by,
           "route_miss": miss_by}
    if kept:
        out.update(blocks=blocks, operators=operators)
    return out
