"""``hybrid_moe_lm`` with its third operator kind on the CPU at a tiny
size: sliding-window layers beside a full one over a window shorter than
the row, a rotary table a kind (YaRN on the full layer), a head width
that is not hidden / heads, softmax-routed experts without a bias, an
untied head; against the plain reference (benchmark/reference_mellum2.py)
on seeded weights, logits and every captured block. The pieces by hand:
the YaRN table, ``route`` with either scoring function, an expert layer
with 8 a token against the plain sum, causality and locality of a
sliding layer; and LFM2's and GLM's specs building what they built."""

import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from mellum2_tiny import ROWS, TINY, YARN, apply, build, reference  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = range(len(TINY["layer_types"]))


@pytest.fixture(scope="module")
def tiny():
    module, params = build()
    return module, params, reference.forward(params, ROWS, TINY,
                                             keep_blocks=True)


def test_registry_builds_the_family_with_its_new_keys():
    from mmlspark_tpu.models.hybrid_moe_lm import OPERATORS, HybridMoELM
    from mmlspark_tpu.models.networks import build_network
    assert OPERATORS == ("conv", "full_attention", "sliding_attention",
                         "mamba")                 # the fourth: Mamba-2
    module = build_network({"dtype": "bfloat16", **TINY})
    assert isinstance(module, HybridMoELM)
    cfg = module.cfg
    # given, not hidden / heads (64 / 8 = 8)
    assert cfg.head_dim == 16 and cfg.sliding_window == 8
    assert cfg.rope_for("sliding_attention") == {
        "rope_type": "default", "rope_theta": 10000.0}
    assert cfg.rope_for("full_attention") == YARN
    assert (cfg.scoring_func, cfg.use_expert_bias,
            cfg.tie_word_embeddings) == ("softmax", False, False)
    assert module.feature_layers() == (
        [f"block_{i}" for i in LAYERS] + [f"operator_{i}" for i in LAYERS]
        + [f"routed_{i}" for i in LAYERS] + ["final"])
    assert hash(module) == hash(build_network({"dtype": "bfloat16", **TINY}))


@pytest.mark.parametrize("bad", [
    {"sliding_window": 0},                          # a sliding layer needs one
    {"head_dim": 15},                               # rotate-half pairs
    {"scoring_func": "tanh"},
    {"rope_parameters": {"conv": {"rope_type": "default"}}},
    {"rope_parameters": {"full_attention": {"rope_type": "ntk"}}},
    {"layer_types": ["sliding_attention", "window"]}])
def test_a_spec_that_makes_no_model_is_refused(bad):
    from mmlspark_tpu.models.networks import build_network
    with pytest.raises(ValueError):
        build_network({**TINY, **bad})


def test_the_parameter_tree(tiny):
    _, params, _ = tiny
    assert params["lm_head"].shape == params["embed"].shape == (128, 64)
    attn = params["layer_0_attn"]
    assert attn["q_proj"].shape == (64, 8, 16)
    assert attn["k_proj"].shape == attn["v_proj"].shape == (64, 2, 16)
    assert attn["out_proj"].shape == (8, 16, 64)
    assert attn["q_layernorm"].shape == attn["k_layernorm"].shape == (16,)
    # no bias where the configuration has none, no dense layer, no conv
    assert sorted(params["layer_2_moe"]) == [
        "experts_down", "experts_gate", "experts_up", "router"]
    assert not any("mlp" in k or "conv" in k for k in params)


def test_logits_match_the_reference(tiny):
    module, params, ref = tiny
    got = apply(module, params, ROWS)
    assert got.shape == (3, 128) and got.dtype == np.float32
    assert np.linalg.norm(got - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])


@pytest.mark.parametrize("i", LAYERS)
def test_each_block_operator_and_choice_match_the_reference(tiny, i):
    module, params, ref = tiny
    for name, theirs in ((f"block_{i}", ref["blocks"][i]),
                         (f"operator_{i}", ref["operators"][i])):
        got = apply(module, params, ROWS, capture=name)
        assert np.linalg.norm(got - theirs) \
            < 1e-5 * np.linalg.norm(theirs), name
    chosen = apply(module, params, ROWS, capture=f"routed_{i}")
    assert chosen.shape == (3, 48, 8)
    assert (np.sort(chosen, -1) == np.sort(ref["routed"][i], -1)).all()


def test_the_untied_head_is_the_head(tiny):
    module, params, _ = tiny
    final = apply(module, params, ROWS, capture="final")
    np.testing.assert_allclose(apply(module, params, ROWS),
                               final @ np.asarray(params["lm_head"]).T,
                               rtol=1e-5, atol=1e-5)
    tied, tied_params = build(tie_word_embeddings=True)
    assert "lm_head" not in tied_params


def test_bfloat16_stays_near_the_reference(tiny):
    _, params, ref = tiny
    module, _ = build("bfloat16")
    cast = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = apply(module, cast, ROWS)
    want = reference.forward(cast, ROWS, TINY)["logits"]
    assert got.dtype == np.float32
    assert np.linalg.norm(got - want) < 0.05 * np.linalg.norm(want)


# ---------------------------------------------------- the window, by position

def test_a_sliding_layer_is_causal_and_local(tiny):
    """Layer 0's operator at position t reads tokens t - 7 .. t (a
    window of 8 counts the query itself): changing token t + 1, or
    token t - 8, leaves it bit for bit; changing token t - 7 does not."""
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
    # the full layer reads every earlier token: the model as a whole is
    # causal, and no more local than that
    full = apply(module, params, ROWS, capture="operator_2")
    rows = ROWS.copy()
    rows[:, 0] = (rows[:, 0] + 1) % 128
    assert not np.array_equal(
        apply(module, params, rows, capture="operator_2")[:, t], full[:, t])


def test_the_window_is_the_spec_s(tiny):
    _, params, ref = tiny
    wide, _ = build(sliding_window=48)      # every earlier key: causal
    got = apply(wide, params, ROWS, capture="operator_0")
    want = reference.forward(params, ROWS, TINY, keep_blocks=[0],
                             window=None)["operators"][0]
    assert np.linalg.norm(got - want) < 1e-5 * np.linalg.norm(want)
    assert np.linalg.norm(got - ref["operators"][0]) \
        > 0.1 * np.linalg.norm(want)


# ------------------------------------------------------- the rotary tables

def test_the_yarn_table_by_hand():
    """Mellum2's: 64 pairs, theta 5e5, factor 16 over 8192 positions."""
    from mmlspark_tpu.models.hybrid_moe_lm import yarn_table
    inv, factor = yarn_table(128, 500000.0, 16.0, 8192, 32.0, 1.0,
                             1.2772588722239782)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    base = 500000.0 ** (-np.arange(64) / 64.0)
    assert inv.dtype == np.float32 and inv.shape == (64,)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-6)    # 0-18
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    i = 27                                          # between the bounds
    ramp = (i - 18) / (35 - 18)
    np.testing.assert_allclose(
        inv[i], (1 - ramp) * base[i] + ramp * base[i] / 16, rtol=1e-6)
    assert (np.diff(inv) < 0).all() and factor == 1.2772588722239782
    # the factor where the config does not give it: 0.1 ln(factor) + 1
    assert yarn_table(128, 5e5, 16.0, 8192)[1] == pytest.approx(
        0.1 * math.log(16) + 1) == pytest.approx(1.2772588722239782)
    # ... and the reference's table is the same, from the same formulas
    spec = {"rope_parameters": {"full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}}}
    theirs, their_factor = reference.rope_table(spec, "full_attention", 128)
    np.testing.assert_allclose(inv, theirs, rtol=1e-6)
    assert their_factor == factor


def test_the_rotation_with_a_table_and_the_default_unchanged():
    from mmlspark_tpu.models.hybrid_moe_lm import (
        rope_rotate_half, yarn_table)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 3, 16))
    pos = jnp.arange(40)
    inv = 10000.0 ** (-np.arange(0, 16, 2, dtype=np.float32) / 16)
    ang = np.arange(40, dtype=np.float32)[:, None] * inv[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    a, b = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    plain = rope_rotate_half(x, pos, 10000.0)
    np.testing.assert_allclose(plain, want, rtol=1e-5, atol=1e-5)
    # the default table's program is what it was: theta and nothing else
    text = str(jax.make_jaxpr(lambda x: rope_rotate_half(x, pos, 1e4))(x))
    assert text == str(jax.make_jaxpr(
        lambda x: rope_rotate_half(x, pos, theta=1e4))(x))
    assert "pow" in text and " mul " in text
    # a table in theta's place, cos and sin both times the factor
    yinv, factor = yarn_table(16, **YARN)
    got = rope_rotate_half(x, pos, 10000.0, yinv, factor)
    ang = np.arange(40, dtype=np.float32)[:, None] * yinv[None]
    cos = factor * np.cos(ang)[None, :, None]
    sin = factor * np.sin(ang)[None, :, None]
    np.testing.assert_allclose(
        got, np.concatenate([a * cos - b * sin, b * cos + a * sin], -1),
        rtol=1e-5, atol=1e-5)
    # a rotation scaled: every pair's length grows by the factor
    np.testing.assert_allclose(
        np.hypot(got[..., :8], got[..., 8:]), factor * np.hypot(a, b),
        rtol=1e-4, atol=1e-5)


def test_each_kind_turns_by_its_own_table(tiny):
    """The full layer by YaRN's, the sliding layers by the default one:
    the reference with the default table on the full layer differs at
    layer 2 and nowhere before it."""
    _, params, ref = tiny
    plain = reference.forward(params, ROWS, TINY, keep_blocks=True,
                              yarn=False)
    for i in (0, 1):
        np.testing.assert_array_equal(plain["operators"][i],
                                      ref["operators"][i])
    assert np.linalg.norm(plain["operators"][2] - ref["operators"][2]) \
        > 0.05 * np.linalg.norm(ref["operators"][2])


# ----------------------------------------------------------- the router

def parent_route(u, router, bias, k, scaling, norm_eps=0.0):
    """``expert_layer.route`` as it was before it knew a second scoring
    function (commit 58ad1bc), to the letter."""
    logits = jnp.einsum("td,ed->te", u.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + bias[None, :], k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(picked, -1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    gates = scaling * picked / total
    return chosen, gates


def test_route_with_softmax_scores_and_with_sigmoid_as_it_was():
    from mmlspark_tpu.models.expert_layer import route
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    router = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    chosen, gates = route(u, router, None, 8, 1.0, 0.0, "softmax")
    score = np.asarray(jax.nn.softmax(
        np.asarray(u, np.float64) @ np.asarray(router, np.float64).T, -1))
    order = np.argsort(-score, -1)[:, :8]
    assert (np.sort(chosen, -1) == np.sort(order, -1)).all()
    picked = np.take_along_axis(score, np.asarray(chosen), -1)
    np.testing.assert_allclose(gates, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    # sigmoid scores: bit for bit the parent's, bias, scaling and all
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(9), (64,))
    for args in ((4, 1.0, 1e-6), (8, 2.5, 0.0)):
        ours = jax.jit(lambda u, r, b: route(u, r, b, *args))(
            u, router, bias)
        theirs = jax.jit(lambda u, r, b: parent_route(u, r, b, *args))(
            u, router, bias)
        for a, b in zip(ours, theirs):
            assert np.array_equal(a, b)
        assert str(jax.make_jaxpr(lambda u, r, b: route(u, r, b, *args))(
            u, router, bias)) == str(jax.make_jaxpr(
                lambda u, r, b: parent_route(u, r, b, *args))(
                    u, router, bias))
    with pytest.raises(KeyError):
        route(u, router, None, 8, 1.0, 0.0, "tanh")


def test_an_expert_layer_with_eight_a_token_is_the_plain_sum():
    from mmlspark_tpu.models.expert_layer import ExpertLayer
    from mmlspark_tpu.models.hybrid_moe_lm import HybridMoEConfig
    spec = {**TINY, "num_experts": 64}          # 8 of 64, as published
    cfg = HybridMoEConfig(**{k: v for k, v in spec.items() if k != "type"},
                          dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    layer = ExpertLayer(cfg)
    params = layer.init(jax.random.PRNGKey(6), u)["params"]
    assert "router_bias" not in params and "shared_0" not in params
    y, chosen, load = layer.apply({"params": params}, u)
    want, ref_chosen, *_ = reference.experts(params, spec, u)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert int(load.sum()) == 48 * 8 and load.shape == (64,)
    # ... which is the sum over the chosen 8 of 64, written out
    score = jax.nn.softmax(u @ params["router"].T, axis=-1)
    plain = np.zeros((48, 64), np.float32)
    for t in range(48):
        picked = score[t, ref_chosen[t]]
        for e, s in zip(np.asarray(ref_chosen[t]), picked):
            h = jax.nn.silu(u[t] @ params["experts_gate"][e]) \
                * (u[t] @ params["experts_up"][e])
            plain[t] += np.asarray(s / picked.sum()
                                   * (h @ params["experts_down"][e]))
    np.testing.assert_allclose(y, plain, rtol=1e-4, atol=1e-5)


# ------------------------------------- what the other configurations build

# sha256 over the sorted "path:shape:dtype" lines of the parameter tree
# that each configuration's networkSpec built at commit 58ad1bc
_TREES = {
    "lfm2-24b-a2b-stage": (
        96, "65c00033378bfde169e2cf627d115c8ec5b19611bc439edd06e9c07488864162"),
    "glm-5.2-ep16": (
        93, "c8ac4191e16e68e2ee159831b0b46d30ae8ca3569b4b6bc5b68c59487dabf3ee"),
}


@pytest.mark.parametrize("config", sorted(_TREES))
def test_the_other_specs_build_the_modules_they_built(config):
    from mmlspark_tpu.models.networks import build_network
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", config + ".json")))["networkSpec"]
    module = build_network({"dtype": "bfloat16", **spec})
    tree = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    lines = sorted(f"{jax.tree_util.keystr(p)}:{a.shape}:{a.dtype}"
                   for p, a in jax.tree_util.tree_flatten_with_path(tree)[0])
    assert (len(lines), hashlib.sha256("\n".join(lines).encode())
            .hexdigest()) == _TREES[config]
    assert any("router_bias" in line for line in lines)
    if config.startswith("lfm2"):
        assert not any("lm_head" in line for line in lines)     # tied
        assert module.cfg.head_dim == 64 and module.cfg.rope_parameters == ()
        assert (module.flash_window_blocks, module.cfg.scoring_func) == (
            0, "sigmoid")


# ------------------------------------------------------------ the normal path

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
    assert np.linalg.norm(out["scores"] - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])
    hists = model.histograms()
    # every routed pair is held: 48 tokens x 8 experts x 3 layers a row
    assert hists["moe_tokens_held"].snapshot()["sum"] == 3 * 48 * 8 * 3
    # every layer is an attention layer and an expert layer
    assert np.asarray(out["routed_tail"]).shape == (3, 3, 16, 8)
    attended = np.asarray(out["attention_tail"])
    assert attended.shape == (3, 3, 4, 64)
    for i in LAYERS:
        np.testing.assert_allclose(attended[:, i],
                                   ref["operators"][i][:, -4:],
                                   rtol=1e-3, atol=1e-5)
    m = model.metrics()
    assert m["moe_gather_combines"] == 3
    # at 48 tokens one fetch block is the row: a block a call, either way
    assert (m["flash_window_blocks"], m["flash_causal_blocks"]) == (1, 1)
    r = PromRenderer()
    pipeline_families(r, model, {})
    text = r.render()
    assert "serving_model_flash_window_blocks 1" in text
    assert "serving_model_flash_causal_blocks 1" in text
