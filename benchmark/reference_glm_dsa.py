"""The plain reference of the ``glm-5.2-ep16`` configuration: latent
attention over the keys a learned selector picks, sigmoid-routed
experts with one shared expert, RMSNorm, interleaved rotary positions,
gated feed-forwards; next-token logits of the last position. Plain
``jax.numpy`` in float32, every product at highest precision, dense
score matrices, no kernels. Written from the equations (ISSUE 30,
docs/latent_moe_lm.md); it imports nothing of the program and reads
only a parameter tree and the sizes of ``networkSpec``:

    embed (V, d); final_norm (d,); lm_head (d, V)
    layer_i_attn_norm, layer_i_ffn_norm (d,)
    layer_i_attn/{q_a (d, rq), q_a_norm (rq,), q_b (rq, H, dn+dr),
        kv_a (d, rkv+dr), kv_a_norm (rkv,), kv_b (rkv, H, dn+dv),
        o (H, dv, d)} and, where the layer has a selector,
        {idx_q (rq, J, di), idx_k (d, di), idx_k_norm_scale, _bias (di,),
         idx_w (d, J)}
    layer_i_mlp/{gate, up (d, w), down (w, d)}                   dense
    layer_i_moe/{router (E, d), router_bias (E,), experts_gate,
        experts_up (held, d, w), experts_down (held, w, d),
        shared_0/{gate, up, down}}                               sparse

    u = RMSNorm(x);  x <- x + Attn(u);  x <- x + FFN(RMSNorm(x))
    c_q = RMSNorm(u W_qa); [q_nope | q_rope] = c_q W_qb
    [c_kv | k_r] = u W_kva; c_kv <- RMSNorm(c_kv); [k_nope | v] = c_kv W_kvb
    q = [q_nope | RoPE(q_rope)], k = [k_nope | RoPE(k_r)] (k_r for all heads)
    o_t = W_o concat_h sum_{s in S_t} softmax_{s in S_t}(q_t.k_s/sqrt(dn+dr)) v_s
    selector: q^I = c_q W_qI; k^I = LayerNorm(u W_kI); RoPE on the first dr
      dims of both; w = u W_w J^-1/2 di^-1/2
      I[t,s] = sum_j w[t,j] relu(q^I[t,j] . k^I[s]),  s <= t
      S_t = the index_topk largest I[t,s] (ties to the lower s), all s <= t
      while t < index_topk; a 'shared' layer uses the set of the nearest
      'full' layer below it
    experts: s = sigmoid(u . e_i) over all E; the k largest s + b chosen;
      g = scaling * s / sum_chosen s; y = sum_{chosen, held here} g_i E_i(u)
      + E_shared(u); every E is down(silu(gate u) * up u)

The share: the experts [rank * held, (rank + 1) * held) are here, the
router and the normaliser run over all E, what the others would add is
left out; uncut is held = E, rank 0. It goes layer by layer (a layer's
weights raised to float32 as that layer runs) and row by row, the rows
waiting on the host between layers, attention a few heads at a time,
so that it fits beside the resident bfloat16 weights on one chip.

The controls are the same equations with one thing changed:
``matmul="fp8"`` rounds both operands of every product to float8 e4m3
under one scale a tensor; ``attend="causal"`` attends over every s <= t;
``routed=False`` leaves the routed experts' part out; ``sets="first"``
gives every selector layer the sets of the first one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
LAYER_NORM_EPS = 1e-6
HEAD_BLOCK = 4          # heads whose (l, l) scores are held at a time
                        # (a divisor of the number of heads)


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
    """Interleaved pairs (x[2i], x[2i+1]) of the last axis turned by
    t * theta**(-2i/d); x (l, ..., d), t the row."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def swiglu(u, p, matmul):
    h = jax.nn.silu(_mm("tk,kn->tn", u, p["gate"], matmul)) \
        * _mm("tk,kn->tn", u, p["up"], matmul)
    return _mm("tn,nk->tk", h, p["down"], matmul)


def selected_sets(p, spec, u, c_q, matmul):
    """(l, l) bool: row t true at the keys of S_t."""
    l = u.shape[0]
    k, dr = spec["index_topk"], spec["qk_rope_head_dim"]
    j, di = spec["index_n_heads"], spec["index_head_dim"]
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    if l <= k:
        return causal
    q_i = _mm("lr,rjd->ljd", c_q, p["idx_q"], matmul)
    k_i = _mm("ld,de->le", u, p["idx_k"], matmul)
    mean = k_i.mean(-1, keepdims=True)
    var = ((k_i - mean) ** 2).mean(-1, keepdims=True)
    k_i = (k_i - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) \
        * p["idx_k_norm_scale"] + p["idx_k_norm_bias"]
    q_i = jnp.concatenate([rope(q_i[..., :dr], spec["rope_theta"]),
                           q_i[..., dr:]], -1)
    k_i = jnp.concatenate([rope(k_i[..., :dr], spec["rope_theta"]),
                           k_i[..., dr:]], -1)
    w = _mm("ld,dj->lj", u, p["idx_w"], matmul) * (j ** -0.5 * di ** -0.5)
    if matmul == "fp8":
        q_i, k_i = _fake_fp8(q_i), _fake_fp8(k_i)
    def add_head(score, head):      # one selector head's (l, l) at a time
        q_h, w_h = head
        return score + w_h[:, None] * jax.nn.relu(jnp.einsum(
            "ld,md->lm", q_h, k_i, precision=_HI)), None
    score, _ = jax.lax.scan(add_head, jnp.zeros((l, l), jnp.float32),
                            (q_i.transpose(1, 0, 2), w.T))
    score = jnp.where(causal, score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < k) & causal


def attention(p, spec, u, keep, matmul, attend):
    """u (l, d) normed. Returns (out (l, d), the sets it attended over).
    ``keep`` is the set handed up from the selector layer below; a
    layer with a selector makes its own."""
    l = u.shape[0]
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    dn, rkv = spec["qk_nope_head_dim"], spec["kv_lora_rank"]
    heads = spec["num_attention_heads"]
    c_q = rms_norm(_mm("ld,dr->lr", u, p["q_a"], matmul), p["q_a_norm"], eps)
    q = _mm("lr,rhk->lhk", c_q, p["q_b"], matmul)
    kv = _mm("ld,dr->lr", u, p["kv_a"], matmul)
    c_kv = rms_norm(kv[:, :rkv], p["kv_a_norm"], eps)
    k_r = rope(kv[:, rkv:], theta)
    kv_up = _mm("lr,rhk->lhk", c_kv, p["kv_b"], matmul)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv_up[..., :dn], jnp.broadcast_to(
        k_r[:, None, :], (l, heads, k_r.shape[-1]))], -1)
    v = kv_up[..., dn:]
    if "idx_q" in p and keep is None:
        keep = selected_sets(p, spec, u, c_q, matmul)
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    mask = causal if attend == "causal" else keep
    def block(qkv):                 # HEAD_BLOCK heads' (l, l) scores
        q_b, k_b, v_b = qkv
        s = _mm("hqd,hkd->hqk", q_b, k_b, matmul) \
            / np.sqrt(q.shape[-1]).astype(np.float32)
        prob = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,hkd->hqd", prob, v_b, matmul)

    def blocks(x):                  # (l, H, d) -> (H / B, B, l, d)
        return x.transpose(1, 0, 2).reshape(
            heads // HEAD_BLOCK, HEAD_BLOCK, l, x.shape[-1])
    o = jax.lax.map(block, (blocks(q), blocks(k), blocks(v)))
    o = o.reshape(heads, l, v.shape[-1]).transpose(1, 0, 2)
    return _mm("lhv,hvd->ld", o, p["o"], matmul), keep


def experts(p, spec, u, matmul, routed=True):
    """u (t, d). Returns (y (t, d), chosen (t, k), margin (t,)): the
    margin is how far score + bias would have to move for an expert
    held here to enter or leave the chosen k (the least gap between a
    chosen and an unchosen expert of which one is held here; uncut, the
    k-th largest less the next). A choice that differs only among the
    other chips' experts moves nothing here but the gates' normaliser."""
    held, rank = spec["experts_held"], spec["expert_rank"]
    k = spec["num_experts_per_tok"]
    logits = jnp.einsum("td,ed->te", u, p["router"], precision=_HI)
    score = jax.nn.sigmoid(logits)
    biased = score + p["router_bias"]
    order = jnp.argsort(-biased, axis=-1, stable=True)
    chosen = order[:, :k]
    ranked = jnp.take_along_axis(biased, order[:, :k + 1], axis=-1)
    is_chosen = jnp.argsort(order, axis=-1) < k
    here = (jnp.arange(biased.shape[1]) // held) == rank
    margin = jnp.minimum(
        jnp.min(jnp.where(is_chosen & here, biased, jnp.inf), -1)
        - ranked[:, k],
        ranked[:, k - 1]
        - jnp.max(jnp.where(~is_chosen & here, biased, -jnp.inf), -1))
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    gate = spec["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)
    y = swiglu(u, p["shared_0"], matmul)
    if not routed:
        return y, chosen, margin

    def add_expert(y, expert):
        # every token through expert e, weighted by its gate (0 where
        # the token did not choose it): dense, and plainly the sum
        e, weights = expert
        g = jnp.sum(jnp.where(chosen == rank * held + e, gate, 0.0), -1)
        return y + g[:, None] * swiglu(u, weights, matmul), None
    y, _ = jax.lax.scan(add_expert, y, (jnp.arange(held), {
        n: p["experts_" + n] for n in ("gate", "up", "down")}))
    return y, chosen, margin


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layer(params, i):
    return {k[len(f"layer_{i}_"):]: v for k, v in params.items()
            if k.startswith(f"layer_{i}_")}


@functools.partial(jax.jit, static_argnames=(
    "spec", "matmul", "attend", "routed", "make_sets"))
def _layer_row(p, x, keep, *, spec, matmul, attend, routed, make_sets):
    spec = dict(spec)
    eps = spec["rms_norm_eps"]
    p = _f32(p)        # raised here, a layer at a time, as it is used
    a, keep = attention(p["attn"], spec, rms_norm(x, p["attn_norm"], eps),
                        None if make_sets else keep, matmul, attend)
    x = x + a
    u = rms_norm(x, p["ffn_norm"], eps)
    if "mlp" in p:
        return x + swiglu(u, p["mlp"], matmul), keep, None
    y, chosen, margin = experts(p["moe"], spec, u, matmul, routed)
    return x + y, keep, (chosen, margin)


def forward(params, tokens, spec, matmul="f32", attend="selected",
            routed=True, sets="own") -> dict:
    """tokens (n, l) ids of the slice. Returns {"logits": (n, V) float32,
    "selected": {layer: (n, l, l) bool}, "routed": {layer: (n, l, k)},
    "router_margin": {layer: (n, l)}} as numpy arrays; ``selected``
    holds the layers with a selector, the other two the expert layers
    (the margin says how near a tie the choice was for the experts
    held here: see ``experts``)."""
    tokens = np.asarray(tokens)
    sizes = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)))
    kinds = list(zip(spec["mlp_layer_types"], spec["indexer_types"]))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], jnp.float32)
        # rows wait on the host between layers: the chip holds the
        # resident weights, one row and one layer's temporaries
        xs = [np.asarray(embed[jnp.asarray(row)]) for row in tokens]
        del embed
        keeps = [None] * len(xs)
        first_sets = None
        selected, chosen_by, margin_by = {}, {}, {}
        for i, (_, indexer) in enumerate(kinds):
            p = _layer(params, i)
            own = indexer == "full" and not (sets == "first"
                                             and first_sets is not None)
            if indexer == "full" and not own:
                keeps = list(first_sets)
            routed_rows = []
            for r, x in enumerate(xs):
                x, keep, routed_row = _layer_row(
                    p, x, keeps[r], spec=sizes, matmul=matmul,
                    attend=attend, routed=routed, make_sets=own)
                xs[r], keeps[r] = np.asarray(x), np.asarray(keep)
                routed_rows.append(routed_row)
            if indexer == "full":
                selected[i] = np.stack(keeps)
                if first_sets is None:
                    first_sets = list(keeps)
            if routed_rows[0] is not None:
                chosen_by[i], margin_by[i] = (
                    np.stack([np.asarray(row[j]) for row in routed_rows])
                    for j in (0, 1))
            del p
        last = jnp.asarray(np.stack([x[-1] for x in xs]))
        last = rms_norm(last, jnp.asarray(params["final_norm"], jnp.float32),
                        spec["rms_norm_eps"])
        logits = _mm("bd,dv->bv", last,
                     jnp.asarray(params["lm_head"], jnp.float32), matmul)
    return {"logits": np.asarray(logits), "selected": selected,
            "routed": chosen_by, "router_margin": margin_by}
