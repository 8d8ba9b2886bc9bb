"""The plain reference of the ``granite-4.0-h-micro`` configuration: a
decoder whose layers are Mamba-2 state-space layers and, at the places
``layer_types`` gives, grouped-query attention with no positions at all,
a dense gated feed-forward in every layer, the embedding, the branches
and the logits scaled by the µP multipliers, a tied head; next-token
logits of the last position. Plain ``jax.numpy`` in float32, every
product at highest precision; the state-space layer the **sequential
recurrence** over positions (``lax.scan`` over t, one state update a
token), attention a dense masked product in blocks of queries; no
kernels, no chunks. Written from the equations (the Mamba-2 paper's SSD
layer, the published ``granitemoehybrid`` config, docs/hybrid_moe_lm.md);
it imports nothing of the program and reads only a parameter tree and
the sizes of ``networkSpec``:

    embed (V, d); embedding_norm (d,)
    layer_i_operator_norm, layer_i_ffn_norm (d,)
    layer_i_mamba/{in_proj (d, inner + C + H), conv (K, C), conv_bias (C,),
        dt_bias, A_log, D (H,), norm (inner,), out_proj (inner, d)}
        inner = mamba_expand d = H P, C = inner + 2 G N
    layer_i_attn/{q_proj (d, Hq, Dh), k_proj, v_proj (d, Hkv, Dh),
        out_proj (Hq, Dh, d)}
    layer_i_mlp/{gate, up (d, f), down (f, d)}

    x0 = m_e embed[tokens]
    h  = x + m_r Op(N(x; operator_norm));  x' = h + m_r MLP(N(h; ffn_norm))
    logits = embed N(x[last]; embedding_norm) / logits_scaling
    mamba: [z | xBC | dt] = u W_in; xBC = silu(conv(xBC) + b), the conv
      causal and depthwise, tap K - 1 on the present; [x | B | C] = xBC;
      dt = softplus(dt + dt_bias); A = -exp(A_log); for t = 0 .. l - 1,
      S = exp(dt_t A) S + dt_t x_t B_t^T (S (H, P, N), from 0), head h
      reading group h // (H / G); y_t = S C_t + D x_t;
      Op = RMSNorm(y * silu(z); norm) W_out, the norm over each group
    attention: q = u W_q, k = u W_k, v = u W_v, no norm, no rotary step;
      key/value head h // (Hq / Hkv) serves query head h; query p sees
      key j where j <= p; o = softmax(q k^T attention_multiplier) v;
      Op = o W_o
    MLP = down(silu(gate u) * up u)

It goes layer by layer and row by row, the rows waiting on the host
between layers, each layer raised to float32 as it runs.

The controls are the same equations with one thing changed (``CONTROLS``
holds the sound values): ``matmul="fp8"`` rounds both operands of every
matrix product to float8 e4m3 under one scale a tensor;
``carry=False`` starts the state from zero at every mamba_chunk_size
boundary; ``decay=False`` takes exp(dt A) as 1; ``dt_bias=False`` leaves
dt_bias out; ``d_skip=False`` leaves D x out; ``gated_norm="after"``
takes RMSNorm(y) * silu(z); ``conv_taps="reversed"`` turns the taps
round; ``conv_bias=False`` leaves the conv's bias out; ``swap_bc=True``
swaps B and C; ``residual_multiplier=False``,
``embedding_multiplier=False`` and ``logits_scaling=False`` take each
as 1; ``attention_scale="sqrt"`` scales the scores by 1 / sqrt(Dh);
``qk_norm=True`` puts a per-head RMSNorm of unit gain on q and k;
``rope=True`` turns q and k by the default rotary table at
``rope_theta``; ``kv_head="mod"`` gives query head h the key/value head
h % Hkv.
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
CONTROLS = {"matmul": "f32", "carry": True, "decay": True,
            "dt_bias": True, "d_skip": True, "gated_norm": "before",
            "conv_taps": "as_held", "conv_bias": True, "swap_bc": False,
            "residual_multiplier": True, "embedding_multiplier": True,
            "logits_scaling": True, "attention_scale": "spec",
            "qk_norm": False, "rope": False, "kv_head": "group"}
KINDS = ("mamba", "full_attention")


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


def silu(x):
    return x * jax.nn.sigmoid(x)


def sizes(spec) -> dict:
    d = spec["hidden_size"]
    inner = spec["mamba_expand"] * d
    groups, state = spec["mamba_n_groups"], spec["mamba_d_state"]
    return {"inner": inner, "channels": inner + 2 * groups * state,
            "heads": spec["mamba_n_heads"], "width": spec["mamba_d_head"],
            "groups": groups, "state": state}


def recurrence(x, dt, a, b, c, chunk=None, decay=True):
    """The scan by stepping through t: x (l, H, P), dt (l, H), a (H,),
    b, c (l, H, N) (already a head's group). Returns (y (l, H, P), the
    final state (H, P, N)). ``chunk`` starts the state from zero at
    every multiple of it (a control); ``decay=False`` keeps it whole."""
    heads, width = x.shape[1:]

    def step(s, at):
        t, x_t, dt_t, b_t, c_t = at
        if chunk:
            s = jnp.where(t % chunk == 0, 0.0, s)
        kept = jnp.exp(dt_t * a) if decay else jnp.ones_like(dt_t)
        s = kept[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=_HI)
    s0 = jnp.zeros((heads, width, b.shape[-1]), jnp.float32)
    final, y = jax.lax.scan(step, s0, (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, final


def mamba_operator(p, spec, u, matmul="f32", carry=True, decay=True,
                   gated_norm="before", swap_bc=False):
    """u (l, d) normed -> (l, d)."""
    n = sizes(spec)
    length, inner, channels = u.shape[0], n["inner"], n["channels"]
    heads, groups = n["heads"], n["groups"]
    zxd = _mm("ld,de->le", u, p["in_proj"], matmul)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + channels],
                  zxd[:, inner + channels:])
    taps = p["conv"]
    k = taps.shape[0]
    past = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(taps[j] * past[j:j + length] for j in range(k))
    if "conv_bias" in p:
        conv = conv + p["conv_bias"]
    xbc = silu(conv)
    split = inner + groups * n["state"]
    x = xbc[:, :inner].reshape(length, heads, n["width"])
    b = xbc[:, inner:split].reshape(length, groups, n["state"])
    c = xbc[:, split:].reshape(length, groups, n["state"])
    if swap_bc:
        b, c = c, b
    serves = np.arange(heads) // (heads // groups)
    step = jax.nn.softplus(dt + p["dt_bias"])
    y, _ = recurrence(x, step, -jnp.exp(p["A_log"]), b[:, serves],
                      c[:, serves],
                      None if carry else spec["mamba_chunk_size"], decay)
    y = y + p["D"][:, None] * x
    y = y.reshape(length, groups, inner // groups)
    z = z.reshape(length, groups, inner // groups)
    gain = p["norm"].reshape(groups, -1)
    eps = spec["norm_eps"]
    if gated_norm == "before":
        y = rms_norm(y * silu(z), gain, eps)
    else:
        y = rms_norm(y, gain, eps) * silu(z)
    return _mm("le,ed->ld", y.reshape(length, inner), p["out_proj"], matmul)


def rope(x, theta):
    """The pairs (x[i], x[i + D/2]) of the last axis turned by
    t theta^(-2i/D); x (l, heads, D), t the row (a control)."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_operator(p, spec, u, matmul="f32", attention_scale="spec",
                       qk_norm=False, rope_on=False, kv_head="group"):
    """u (l, d) normed -> (l, d)."""
    length = u.shape[0]
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    q = _mm("ld,dhk->lhk", u, p["q_proj"], matmul)
    k = _mm("ld,dhk->lhk", u, p["k_proj"], matmul)
    v = _mm("ld,dhk->lhk", u, p["v_proj"], matmul)
    width = q.shape[-1]
    if qk_norm:
        one = jnp.ones((width,), jnp.float32)
        q, k = (rms_norm(t, one, spec["norm_eps"]) for t in (q, k))
    if rope_on:
        q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    serves = np.arange(heads) // (heads // kv) if kv_head == "group" \
        else np.arange(heads) % kv
    k, v = k[:, serves], v[:, serves]           # (l, H, D), plainly repeated
    scale = spec.get("attention_multiplier") \
        if attention_scale == "spec" else None
    if scale is None:
        scale = float(width) ** -0.5
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length

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


def swiglu(u, gate, up, down, matmul):
    h = silu(_mm("tk,kn->tn", u, gate, matmul)) \
        * _mm("tk,kn->tn", u, up, matmul)
    return _mm("tn,nk->tk", h, down, matmul)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layer(params, i):
    return {k[len(f"layer_{i}_"):]: v for k, v in params.items()
            if k.startswith(f"layer_{i}_")}


# the controls a kind's program is traced with; the others are a change
# of its data (``_controlled``), so that a stand-in costs no compile
TRACED = {"mamba": ("matmul", "carry", "decay", "gated_norm", "swap_bc"),
          "full_attention": ("matmul", "attention_scale", "qk_norm", "rope",
                             "kv_head")}


def _controlled(p, kind, c):
    """A layer's parameters with the stand-ins that are a change of its
    data put in: the conv's taps reversed, its bias, dt_bias or D at
    zero."""
    if kind != "mamba":
        return p
    m = dict(p["mamba"])
    if c["conv_taps"] == "reversed":
        m["conv"] = jnp.asarray(m["conv"])[::-1]
    for key, on in (("conv_bias", c["conv_bias"]), ("dt_bias", c["dt_bias"]),
                    ("D", c["d_skip"])):
        if not on and key in m:
            m[key] = jnp.zeros_like(m[key])
    return {**p, "mamba": m}


@functools.partial(jax.jit, static_argnames=("kind", "spec", "controls"))
def _layer_row(p, x, rm, *, kind, spec, controls):
    """One row through one layer -> (x', the operator's output); ``rm``
    the residual multiplier."""
    spec, c = json.loads(spec), dict(controls)     # hashable for the jit
    eps, mm = spec["norm_eps"], c["matmul"]
    p = _f32(p)
    u = rms_norm(x, p["operator_norm"], eps)
    if kind == "mamba":
        a = mamba_operator(p["mamba"], spec, u, mm, c["carry"], c["decay"],
                           c["gated_norm"], c["swap_bc"])
    else:
        a = attention_operator(p["attn"], spec, u, mm, c["attention_scale"],
                               c["qk_norm"], c["rope"], c["kv_head"])
    x = x + rm * a
    m = p["mlp"]
    y = swiglu(rms_norm(x, p["ffn_norm"], eps), m["gate"], m["up"],
               m["down"], mm)
    return x + rm * y, a


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(last, gain, head, divide, *, eps, matmul):
    """logits = head N(x[last]; gain) / divide, in one program."""
    last = rms_norm(last, gain.astype(jnp.float32), eps)
    return _mm("bd,vd->bv", last, head.astype(jnp.float32), matmul) / divide


def forward(params, tokens, spec, keep_blocks=False, keep_tail=None,
            **controls) -> dict:
    """tokens (n, l) ids. Returns {"logits": (n, V) float32} as numpy
    arrays; with ``keep_blocks`` (True, or the layers to keep) also
    "blocks" and "operators" {layer: (n, l, d)}: the hidden state after
    each layer and each operator's output, their last ``keep_tail``
    positions alone where that is given. ``controls`` are the stand-ins
    of the module's docstring."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise TypeError(f"unknown controls {sorted(unknown)}")
    controls = {**CONTROLS, **controls}
    tokens = np.asarray(tokens)
    kinds = list(spec["layer_types"])
    if set(kinds) - set(KINDS):
        raise ValueError(f"this reference knows {KINDS} layers and no "
                         f"other")
    if spec.get("num_dense_layers", len(kinds)) != len(kinds):
        raise ValueError("this reference knows a dense feed-forward in "
                         "every layer and no expert layer")
    shapes = json.dumps({k: v for k, v in spec.items()
                         if k not in ("layer_types", "type", "dtype")},
                        sort_keys=True)
    kept = range(len(kinds)) if keep_blocks is True \
        else tuple(keep_blocks or ())
    tail = slice(None) if keep_tail is None else slice(-keep_tail, None)
    scale = (spec.get("embedding_multiplier") or 1.0) \
        if controls["embedding_multiplier"] else 1.0
    divide = spec.get("logits_scaling", 1.0) \
        if controls["logits_scaling"] else 1.0
    rm = np.float32(spec.get("residual_multiplier", 1.0)
                    if controls["residual_multiplier"] else 1.0)
    mm = controls["matmul"]
    traced = {kind: tuple((k, controls[k]) for k in names)
              for kind, names in TRACED.items()}
    with jax.default_matmul_precision("highest"):
        # rows wait on the host between layers: the chip holds the
        # resident weights, one row and one layer's temporaries
        xs = [np.asarray(jnp.asarray(params["embed"])[jnp.asarray(row)]
                         .astype(jnp.float32) * scale) for row in tokens]
        blocks, operators = {}, {}
        for i, kind in enumerate(kinds):
            p = _controlled(_layer(params, i), kind, controls)
            ops = []
            for r, x in enumerate(xs):
                x, a = _layer_row(dict(p), x, rm, kind=kind, spec=shapes,
                                  controls=traced[kind])
                xs[r] = np.asarray(x)
                if i in kept:
                    ops.append(np.asarray(a[tail]))
            if i in kept:
                blocks[i] = np.stack([x[tail] for x in xs])
                operators[i] = np.stack(ops)
            del p
        logits = _head(jnp.asarray(np.stack([x[-1] for x in xs])),
                       params["embedding_norm"], params["embed"],
                       np.float32(divide), eps=spec["norm_eps"], matmul=mm)
    out = {"logits": np.asarray(logits)}
    if kept:
        out.update(blocks=blocks, operators=operators)
    return out
