"""``hybrid_moe_lm`` with what Trinity-Mini asks of it, on the CPU at a
tiny size: an output gate on attention, a kind of layer with no rotary
table, four norms a layer, a scaled embedding, a shared expert beside
the routed ones, a dense layer whose operator is attention; against the
plain reference (benchmark/reference_trinity.py) on seeded weights,
logits and every captured block. Each new key alone against the
equations by hand; causality and locality of a sliding layer; ``route``
at 128 experts, 8 a token; an expert layer with a shared expert against
the plain sum; the windowed flash forward at a window of two fetch
blocks; and LFM2's, Mellum2's and GLM's specs building, and tracing to,
what they did at the parent commit (baf2ee0)."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trinity_tiny import ROWS, TINY, apply, build, reference  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = range(len(TINY["layer_types"]))
EPS = TINY["norm_eps"]


@pytest.fixture(scope="module")
def tiny():
    module, params = build()
    return module, params, reference.forward(params, ROWS, TINY,
                                             keep_blocks=True)


def near(got, want, tol=1e-5):
    return np.linalg.norm(np.asarray(got, np.float64) - want) \
        < tol * np.linalg.norm(want)


def norm(x, gain):
    x = np.asarray(x, np.float64)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) \
        * np.asarray(gain, np.float64)


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def silu(x):
    return x * sigmoid(x)


def attention_by_hand(p, u, window=None, theta=None, gate=True):
    """The operator written out in float64: u (l, d) normed -> (the
    heads' outputs o (l, H, D) before the gate, the operator's output
    W_o (o * sigmoid(W_g u)))."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    u = np.asarray(u, np.float64)
    length = u.shape[0]
    q = norm(np.einsum("ld,dhk->lhk", u, p["q_proj"]), p["q_layernorm"])
    k = norm(np.einsum("ld,dhk->lhk", u, p["k_proj"]), p["k_layernorm"])
    v = np.einsum("ld,dhk->lhk", u, p["v_proj"])
    d = q.shape[-1]
    if theta is not None:
        ang = np.arange(length)[:, None, None] \
            * theta ** (-np.arange(0, d, 2) / d)
        cos, sin = np.cos(ang), np.sin(ang)

        def turn(x):
            a, b = x[..., :d // 2], x[..., d // 2:]
            return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        q, k = turn(q), turn(k)
    group = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, group, 1), np.repeat(v, group, 1)  # head h // group
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    ago = np.arange(length)[:, None] - np.arange(length)[None, :]
    seen = ago >= 0
    if window:
        seen &= ago < window
    s = np.where(seen, s, -np.inf)
    prob = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hqk,khd->qhd", prob / prob.sum(-1, keepdims=True), v)
    gated = o * sigmoid(np.einsum("ld,dhk->lhk", u, p["gate_proj"])) \
        if gate else o
    return o, np.einsum("lhk,hkd->ld", gated, p["out_proj"])


# ------------------------------------------------ the family, whole

def test_registry_builds_the_family_with_its_new_keys():
    from mmlspark_tpu.models.hybrid_moe_lm import (
        ROPE_TYPES, HybridMoEConfig, HybridMoELM)
    from mmlspark_tpu.models.networks import build_network
    assert ROPE_TYPES == ("default", "yarn", "none")
    module = build_network({"dtype": "bfloat16", **TINY})
    assert isinstance(module, HybridMoELM)
    cfg = module.cfg
    assert (cfg.attention_output_gate, cfg.sandwich_norms, cfg.mup_enabled,
            cfg.num_shared_experts, cfg.n_shared_experts) == (
        True, True, True, 1, 1)
    assert cfg.rope_for("full_attention")["rope_type"] == "none"
    assert cfg.rope_for("sliding_attention") == {
        "rope_type": "default", "rope_theta": 10000.0}
    assert (cfg.routed_scaling_factor, cfg.gate_norm_eps) == (2.826, 1e-20)
    # every new key defaults to what LFM2 and Mellum2 are
    old = HybridMoEConfig()
    assert (old.attention_output_gate, old.sandwich_norms, old.mup_enabled,
            old.num_shared_experts, old.n_shared_experts) == (
        False, False, False, 0, 0)
    assert module.feature_layers() == (
        [f"block_{i}" for i in LAYERS] + [f"operator_{i}" for i in LAYERS]
        + ["routed_1", "routed_2", "final"])
    assert hash(module) == hash(build_network({"dtype": "bfloat16", **TINY}))
    with pytest.raises(ValueError):
        build_network({**TINY, "rope_parameters": {
            "full_attention": {"rope_type": "nope"}}})
    with pytest.raises(TypeError):            # the parent's answer to a new key
        HybridMoEConfig(output_gate=True)


def test_the_parameter_tree(tiny):
    _, params, _ = tiny
    assert params["lm_head"].shape == params["embed"].shape == (128, 64)
    for i in LAYERS:
        attn = params[f"layer_{i}_attn"]
        assert sorted(attn) == ["gate_proj", "k_layernorm", "k_proj",
                                "out_proj", "q_layernorm", "q_proj",
                                "v_proj"]
        assert attn["gate_proj"].shape == attn["q_proj"].shape == (64, 8, 16)
        # four gains a layer, dense layers included
        for name in ("operator_norm", "operator_post_norm", "ffn_norm",
                     "ffn_post_norm"):
            assert params[f"layer_{i}_{name}"].shape == (64,)
    # a leading dense layer whose operator is attention, then experts
    assert sorted(params["layer_0_mlp"]) == ["down", "gate", "up"]
    assert params["layer_0_mlp"]["gate"].shape == (64, 96)
    assert "layer_0_moe" not in params and "layer_1_mlp" not in params
    for i in (1, 2):
        moe = params[f"layer_{i}_moe"]
        assert sorted(moe) == ["experts_down", "experts_gate", "experts_up",
                               "router", "router_bias", "shared_0"]
        assert moe["shared_0"]["gate"].shape == (64, 32)
        assert moe["shared_0"]["down"].shape == (32, 64)
        assert moe["router_bias"].shape == (16,)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import flops_trinity
    assert count == flops_trinity.parameters(TINY)


def test_logits_match_the_reference(tiny):
    module, params, ref = tiny
    got = apply(module, params, ROWS)
    assert got.shape == (3, 128) and got.dtype == np.float32
    assert near(got, ref["logits"])


@pytest.mark.parametrize("i", LAYERS)
def test_each_block_operator_and_choice_match_the_reference(tiny, i):
    module, params, ref = tiny
    for name, theirs in ((f"block_{i}", ref["blocks"][i]),
                         (f"operator_{i}", ref["operators"][i])):
        assert near(apply(module, params, ROWS, capture=name), theirs), name
    if i >= TINY["num_dense_layers"]:
        chosen = apply(module, params, ROWS, capture=f"routed_{i}")
        assert chosen.shape == (3, 48, 4)
        assert (np.sort(chosen, -1) == np.sort(ref["routed"][i], -1)).all()
    else:
        assert i not in ref["routed"]


def test_bfloat16_stays_near_the_reference(tiny):
    _, params, _ = tiny
    module, _ = build("bfloat16")
    cast = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = apply(module, cast, ROWS)
    want = reference.forward(cast, ROWS, TINY)["logits"]
    assert got.dtype == np.float32
    assert near(got, want, 0.06)


# -------------------------------------------- each new key, by hand

def hidden_before(module, params, i):
    """The hidden state that enters layer i, row 0."""
    if i:
        return apply(module, params, ROWS, capture=f"block_{i - 1}")[0]
    return np.sqrt(64.0) * np.asarray(params["embed"], np.float64)[ROWS[0]]


@pytest.mark.parametrize("i", LAYERS)
def test_the_gate_multiplies_the_heads_before_the_output_projection(tiny, i):
    """Op = W_o (o * sigmoid(W_g u)), u the normed input; a sliding
    layer turned by its table and windowed, the full one neither."""
    module, params, _ = tiny
    sliding = TINY["layer_types"][i] == "sliding_attention"
    u = norm(hidden_before(module, params, i),
             params[f"layer_{i}_operator_norm"])
    p = params[f"layer_{i}_attn"]
    _, want = attention_by_hand(p, u, window=8 if sliding else None,
                                theta=10000.0 if sliding else None)
    got = apply(module, params, ROWS, capture=f"operator_{i}")[0]
    assert near(got, want, 2e-5)
    # ... and neither no gate, nor a sigmoid of the same projection
    # after W_o (there is no (d, d) gate: the gate is a head's)
    _, ungated = attention_by_hand(p, u, window=8 if sliding else None,
                                   theta=10000.0 if sliding else None,
                                   gate=False)
    assert np.linalg.norm(got - ungated) > 0.2 * np.linalg.norm(want)
    if i:
        return
    # the key alone: without it no fifth projection, and the operator
    # on the same other weights is the ungated one
    plain, plain_params = build(attention_output_gate=False)
    assert "gate_proj" not in plain_params["layer_0_attn"]
    assert plain.attn_gated_layers == 0 and module.attn_gated_layers == 3
    stripped = {k: {n: w for n, w in v.items() if n != "gate_proj"}
                if k.endswith("_attn") else v for k, v in params.items()}
    assert near(apply(plain, stripped, ROWS, capture="operator_0")[0],
                ungated, 2e-5)


def operator_jaxpr(kind):
    from mmlspark_tpu.models.hybrid_moe_lm import (
        GroupedQueryAttention, HybridMoEConfig)
    cfg = HybridMoEConfig(**{k: v for k, v in TINY.items() if k != "type"},
                          dtype=jnp.float32)
    layer = GroupedQueryAttention(cfg, kind)
    u = jnp.zeros((1, 48, 64), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)
    return str(jax.make_jaxpr(layer.apply)(params, u))


def test_a_kind_with_no_table_takes_no_rotary_step(tiny):
    """The full layer's operator traces to a jaxpr that holds no cos and
    no sin, and equals the table-less attention; the sliding layers'
    still turn."""
    full, sliding = (operator_jaxpr(k) for k in (
        "full_attention", "sliding_attention"))
    assert " cos " not in full and " sin " not in full
    assert "cos" not in full.replace("logistic", "")
    assert " cos " in sliding and " sin " in sliding
    assert "logistic" in full and "logistic" in sliding     # the gate
    module, params, ref = tiny
    u = norm(hidden_before(module, params, 2),
             params["layer_2_operator_norm"])
    _, turned = attention_by_hand(params["layer_2_attn"], u, theta=10000.0)
    _, plain = attention_by_hand(params["layer_2_attn"], u)
    got = apply(module, params, ROWS, capture="operator_2")[0]
    assert near(got, plain, 2e-5)
    assert np.linalg.norm(got - turned) > 0.1 * np.linalg.norm(plain)
    # the reference with the default table on the full layer differs at
    # layer 2 and nowhere before it; without one on the sliding layers,
    # from layer 0 on
    wrong = reference.forward(params, ROWS, TINY, keep_blocks=True,
                              full_rope=True)
    for i in (0, 1):
        np.testing.assert_array_equal(wrong["operators"][i],
                                      ref["operators"][i])
    assert not near(wrong["operators"][2], ref["operators"][2], 0.05)
    still = reference.forward(params, ROWS, TINY, keep_blocks=[0],
                              sliding_rope=False)
    assert not near(still["operators"][0], ref["operators"][0], 0.05)
    # a table for both kinds is the older configurations' model
    both, _ = build(rope_parameters=None)
    assert both.rope_free_layers == 0 and module.rope_free_layers == 1


def test_the_four_norms_at_their_places_and_the_scaled_embedding(tiny):
    """Layer 0 by hand from its captured operator: x0 = sqrt(d) E[t];
    h = x0 + N(a; operator_post_norm); x1 = h + N(FFN(N(h; ffn_norm));
    ffn_post_norm), the dense feed-forward of width 96."""
    module, params, _ = tiny
    f64 = lambda a: np.asarray(a, np.float64)       # noqa: E731
    x0 = hidden_before(module, params, 0)
    a = apply(module, params, ROWS, capture="operator_0")[0]
    h = x0 + norm(a, params["layer_0_operator_post_norm"])
    u = norm(h, params["layer_0_ffn_norm"])
    mlp = params["layer_0_mlp"]
    y = (silu(u @ f64(mlp["gate"])) * (u @ f64(mlp["up"]))) @ f64(mlp["down"])
    want = h + norm(y, params["layer_0_ffn_post_norm"])
    got = apply(module, params, ROWS, capture="block_0")[0]
    assert near(got, want, 2e-5)
    # each piece left out is another model
    assert not near(got, x0 + a + y, 0.1)                   # pre-norm only
    assert not near(got, h + y, 0.1)
    unscaled = x0 / 8 + norm(a, params["layer_0_operator_post_norm"])
    assert not near(got, unscaled + norm(y, params["layer_0_ffn_post_norm"]),
                    0.1)
    # the keys alone: two norms a layer without the post norms, the
    # embedding as held without mup_enabled
    pre, pre_params = build(sandwich_norms=False)
    assert not any("post_norm" in k for k in pre_params)
    stripped = {k: v for k, v in params.items() if "post_norm" not in k}
    u = norm(x0 + a, params["layer_0_ffn_norm"])
    y = (silu(u @ f64(mlp["gate"])) * (u @ f64(mlp["up"]))) @ f64(mlp["down"])
    assert near(apply(pre, stripped, ROWS, capture="block_0")[0],
                x0 + a + y, 2e-5)
    held, held_params = build(mup_enabled=False, gains=False)
    scaled, scaled_params = build(gains=False)
    # drawn at variance 1 / hidden_size where it is scaled, 1 where not
    assert np.std(np.asarray(scaled_params["embed"])) == pytest.approx(
        1 / 8, rel=0.05)
    assert np.std(np.asarray(held_params["embed"])) == pytest.approx(
        1.0, rel=0.05)
    a_scaled = apply(scaled, scaled_params, ROWS, capture="block_0")
    a_held = apply(held, scaled_params, ROWS, capture="block_0")
    assert not near(a_held, a_scaled, 0.1)
    # ... and the reference's controls say the same
    for control in ({"post_norms": False}, {"embed_scale": False}):
        wrong = reference.forward(params, ROWS, TINY, keep_blocks=[0],
                                  **control)
        assert not near(wrong["blocks"][0][0], want, 0.1), control


# ---------------------------------------------- the window, by position

def test_a_sliding_layer_is_causal_and_local(tiny):
    """Layer 0's operator at position t reads tokens t - 7 .. t (a
    window of 8 counts the query itself): changing token t + 1, or
    token t - 8, leaves it bit for bit; changing token t - 7 does not.
    The gate reads position t alone."""
    module, params, _ = tiny
    t = 30
    base = apply(module, params, ROWS, capture="operator_0")

    def changed(at):
        rows = ROWS.copy()
        rows[:, at] = (rows[:, at] + 1) % 128
        return apply(module, params, rows, capture="operator_0")
    assert np.array_equal(changed(t + 1)[:, :t + 1], base[:, :t + 1])
    assert np.array_equal(changed(t - 8)[:, t], base[:, t])
    assert not np.array_equal(changed(t - 7)[:, t], base[:, t])
    assert not np.array_equal(changed(t)[:, t], base[:, t])
    # the full layer has no table and reads every earlier token: order
    # reaches it through the mask and the sliding layers
    full = apply(module, params, ROWS, capture="operator_2")
    rows = ROWS.copy()
    rows[:, 0] = (rows[:, 0] + 1) % 128
    assert not np.array_equal(
        apply(module, params, rows, capture="operator_2")[:, t], full[:, t])
    assert np.array_equal(changed(t + 1)[:, :t + 1], base[:, :t + 1])


def test_the_windowed_flash_forward_at_a_window_of_two_fetch_blocks(
        monkeypatch):
    """Through the interpreted kernel (256 rows a fetch block off the
    chip): a window of 512 over 1280 rows, 8 query heads over 2, against
    the dense masked product; and the cell's plan (a window of 2048 at
    1024-row blocks over 16,384 tokens) held to the boolean mask, block
    by block."""
    from mmlspark_tpu.ops import flash_attention as fa
    from mmlspark_tpu.parallel.ring_attention import dense_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 1280, 8, 32))
    k, v = (jax.random.normal(kk, (1, 1280, 2, 32)) for kk in ks[1:])
    got = fa.flash_attention(q, k, v, causal=True, interpret=True,
                             window=512)
    np.testing.assert_allclose(got, dense_attention(q, k, v, True,
                                                    window=512),
                               rtol=2e-5, atol=2e-5)
    plan = fa.tile_plan(1280, 1280, 32, True, window=512)
    assert plan.band_blocks == 3 and plan.grid == (5, 5)
    monkeypatch.setattr(fa, "_block_caps", lambda d: (1024, 1024))
    plan = fa.tile_plan(16384, 16384, 128, True, window=2048)
    assert (plan.bq, plan.bk, plan.grid, plan.band_blocks) == (
        1024, 1024, (16, 16), 3)
    run = masked = blocks = 0
    for qi in range(16):
        rows = qi * 1024 + np.arange(1024)[:, None]
        for ki in range(16):
            cols = ki * 1024 + np.arange(1024)[None, :]
            seen = (rows >= cols) & (rows - cols < 2048)
            tiles = seen.reshape(1024 // plan.tq, plan.tq,
                                 1024 // plan.tk, plan.tk)
            some, every = tiles.any((1, 3)), tiles.all((1, 3))
            run += some.sum()
            masked += (some & ~every).sum()
            blocks += bool(seen.any())
    counts = plan.counts()
    assert (counts["tiles_run"], counts["tiles_masked"],
            counts["blocks_run"]) == (run, masked, blocks)
    # three key blocks a query block, two of them whole: 45 of 256
    assert (counts["blocks_run"], counts["blocks_grid"]) == (45, 48)
    assert fa.tile_plan(16384, 16384, 128, True).counts()[
        "blocks_run"] == 136


# ---------------------------------------------------------- the router

# sha256 of str(jax.make_jaxpr(route)) at commit baf2ee0 for the two
# older configurations' arguments over (40, 32) bfloat16 tokens and 64
# experts
_ROUTE = {
    "lfm2": ((4, 1.0, 1e-6, "sigmoid"), True,
             "cbef0dc7feef7c0e3041c95290497b3792937a3f4388a51ba377e4463a1410a8"),
    "mellum2": ((8, 1.0, 0.0, "softmax"), False,
                "401359b27979dcb6051b2b4703cfff8701318d29215fe76622f96473cd091c47"),
}


def test_route_at_128_experts_and_as_it_was_for_the_others():
    from mmlspark_tpu.models.expert_layer import route
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    router = jax.random.normal(jax.random.PRNGKey(8), (128, 32)) * 0.3
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(9), (128,))
    chosen, gates = route(u, router, bias, 8, 2.826, 1e-20)
    score = sigmoid(np.asarray(u, np.float64)
                    @ np.asarray(router, np.float64).T)
    order = np.argsort(-(score + np.asarray(bias, np.float64)), -1)[:, :8]
    assert (np.sort(chosen, -1) == np.sort(order, -1)).all()
    # the bias in the choice only: the gates are the scores', over their
    # sum, times 2.826
    picked = np.take_along_axis(score, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        gates, 2.826 * picked / (picked.sum(-1, keepdims=True) + 1e-20),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.826, rtol=1e-5)
    without = np.argsort(-score, -1)[:, :8]
    assert (np.sort(without, -1) != np.sort(order, -1)).any()
    # LFM2's and Mellum2's arguments trace to the parent's jaxpr
    shape = jax.ShapeDtypeStruct
    args = (shape((40, 32), jnp.bfloat16), shape((64, 32), jnp.bfloat16))
    for name, (rest, biased, digest) in _ROUTE.items():
        if biased:
            text = str(jax.make_jaxpr(lambda u, r, b: route(u, r, b, *rest))(
                *args, shape((64,), jnp.float32)))
        else:
            text = str(jax.make_jaxpr(lambda u, r: route(u, r, None, *rest))(
                *args))
        assert hashlib.sha256(text.encode()).hexdigest() == digest, name


def test_an_expert_layer_with_a_shared_expert_is_the_plain_sum():
    from mmlspark_tpu.models.expert_layer import ExpertLayer
    from mmlspark_tpu.models.hybrid_moe_lm import HybridMoEConfig
    spec = {**TINY, "num_experts": 128, "num_experts_per_tok": 8}
    cfg = HybridMoEConfig(**{k: v for k, v in spec.items() if k != "type"},
                          dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    layer = ExpertLayer(cfg)
    params = layer.init(jax.random.PRNGKey(6), u)["params"]
    assert params["router_bias"].shape == (128,) and "shared_0" in params
    assert "shared_1" not in params
    y, chosen, load = layer.apply({"params": params}, u)
    want, ref_chosen, *_ = reference.experts(params, spec, u)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert int(load.sum()) == 48 * 8 and load.shape == (128,)
    # ... which is S(u) + the sum over the chosen 8 of 128, written out
    f64 = lambda a: np.asarray(a, np.float64)       # noqa: E731
    uu = f64(u)
    score = sigmoid(uu @ f64(params["router"]).T)
    s = params["shared_0"]
    plain = (silu(uu @ f64(s["gate"])) * (uu @ f64(s["up"]))) @ f64(s["down"])
    for t in range(48):
        mine = np.asarray(ref_chosen[t])
        picked = score[t, mine]
        for e, sc in zip(mine, picked):
            h = silu(uu[t] @ f64(params["experts_gate"][e])) \
                * (uu[t] @ f64(params["experts_up"][e]))
            plain[t] += 2.826 * sc / (picked.sum() + 1e-20) \
                * (h @ f64(params["experts_down"][e]))
    np.testing.assert_allclose(y, plain, rtol=1e-4, atol=1e-5)
    # the shared expert is added unweighted, and to every token
    none = reference.experts(params, spec, u, shared=False)[0]
    np.testing.assert_allclose(
        np.asarray(want) - np.asarray(none),
        (silu(uu @ f64(s["gate"])) * (uu @ f64(s["up"]))) @ f64(s["down"]),
        rtol=1e-3, atol=1e-4)
    # two shared experts are two gated feed-forwards (one of twice the
    # width, in two halves)
    two = HybridMoEConfig(**{**{k: v for k, v in spec.items()
                                if k != "type"}, "num_shared_experts": 2},
                          dtype=jnp.float32)
    assert sorted(k for k in ExpertLayer(two).init(
        jax.random.PRNGKey(6), u)["params"] if k.startswith("shared")) == [
        "shared_0", "shared_1"]


# --------------------------- what the other configurations build and trace

# sha256 over the sorted "path:shape:dtype" lines of the parameter tree
# that each configuration's networkSpec built at commit baf2ee0, and of
# the whole step's jaxpr over bfloat16 parameters and these token rows.
# Since commit 62cb4ce the passes where every expert is held read their
# rows through their token ids on the chip; off it the gather ``u[tok]``
# moved into ``grouped_swiglu`` and traces to the same operations in
# the same order, so LFM2's and Mellum2's steps are still these
_PARENT = {
    "lfm2-24b-a2b-stage": (
        96, "65c00033378bfde169e2cf627d115c8ec5b19611bc439edd06e9c07488864162",
        (2, 512),
        "e542101ec3334cf555283da72c50387d3acda10651ad0e38dcb502af2cc54553"),
    "mellum2-12b-a2.5b-stage": (
        99, "7b7b5ab10e3acfd59fff25a196bc16310a0aa50e200057d694ca63aa8c8ff665",
        (2, 2048),
        "f192502b8f34d3aff532cdbc9b8af7118ad68047132efd23f12c8dac79310037"),
    "glm-5.2-ep16": (
        93, "c8ac4191e16e68e2ee159831b0b46d30ae8ca3569b4b6bc5b68c59487dabf3ee",
        (2, 512),
        "f2cbb9377f9c6d567133bb46917987a318115e87d25475253afb392c5731b06d"),
}


@pytest.mark.parametrize("config", sorted(_PARENT))
def test_the_other_specs_build_and_trace_to_what_they_did(config):
    from mmlspark_tpu.models.networks import build_network
    leaves, tree_digest, rows, step_digest = _PARENT[config]
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", config + ".json")))["networkSpec"]
    module = build_network({"dtype": "bfloat16", **spec})
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    lines = sorted(
        f"{jax.tree_util.keystr(p)}:{a.shape}:{a.dtype}" for p, a in
        jax.tree_util.tree_flatten_with_path(variables["params"])[0])
    assert (len(lines), hashlib.sha256("\n".join(lines).encode())
            .hexdigest()) == (leaves, tree_digest)
    assert not any("gate_proj" in line or "post_norm" in line
                   or "shared" in line and not config.startswith("glm")
                   for line in lines)
    text = str(jax.make_jaxpr(
        lambda v, t: module.apply(v, t, mutable=["stats"]))(
        variables, jax.ShapeDtypeStruct(rows, jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() == step_digest
    assert (getattr(module, "attn_gated_layers", 0),
            getattr(module, "rope_free_layers", 0)) == (0, 0)
    assert module.moe_shared_experts == (1 if config.startswith("glm")
                                         else 0)


# --------------------------------------------------------- the normal path

def test_through_tpu_model_with_its_counters(tiny):
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    module, params, ref = tiny
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=2)
    model.set("fetchDict", {"scores": "output",
                            "routed_tail": "routed_tail",
                            "attention_tail": "attention_tail"})
    out = model.transform(DataTable({"features": ROWS.astype(np.float32)}))
    assert near(out["scores"], ref["logits"])
    hists = model.histograms()
    # every routed pair is held: 48 tokens x 4 experts x 2 expert layers
    assert hists["moe_tokens_held"].snapshot()["sum"] == 3 * 48 * 4 * 2
    # two expert layers, three attention layers (the dense one's too)
    assert np.asarray(out["routed_tail"]).shape == (3, 2, 16, 4)
    attended = np.asarray(out["attention_tail"])
    assert attended.shape == (3, 3, 4, 64)
    for i in LAYERS:            # the operator's output, before its norm
        np.testing.assert_allclose(attended[:, i],
                                   ref["operators"][i][:, -4:],
                                   rtol=1e-3, atol=1e-5)
    m = model.metrics()
    assert (m["attn_gated_layers"], m["rope_free_layers"],
            m["moe_shared_experts"]) == (3, 1, 1)
    assert m["moe_gather_combines"] == m["moe_fused_swiglu_layers"] == 2
    r = PromRenderer()
    pipeline_families(r, model, {})
    text = r.render()
    assert "serving_model_attn_gated_layers 3" in text
    assert "serving_model_rope_free_layers 1" in text
    assert "serving_model_moe_shared_experts 1" in text
    # a module without the three reads 0 and exports it
    from mellum2_tiny import build as build_mellum2
    other, other_params = build_mellum2()
    plain = TPUModel.from_flax(other, {"params": other_params},
                               inputCol="features", outputCol="scores",
                               batchSize=2)
    m = plain.metrics()
    assert (m["attn_gated_layers"], m["rope_free_layers"],
            m["moe_shared_experts"]) == (0, 0, 0)
