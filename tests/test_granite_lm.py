"""``hybrid_moe_lm`` with what Granite-4.0-H-Micro asks of it, on the CPU
at a tiny size: Mamba-2 state-space layers among position-free
attention layers with no q/k norms and a scale of their own, a dense
feed-forward in every layer and no expert, the µP multipliers; against
the plain reference (benchmark/reference_granite.py, whose scan is the
sequential recurrence) on seeded weights, logits and every captured
block and operator. The chunked scan against the recurrence at several
chunks; causality; the conv, the gated norm, the attention scale and
the multipliers each by hand; the family with no expert layer; the
counters and ``ssm_tail`` through TPUModel; and LFM2's, Mellum2's,
Trinity's and GLM's specs building, and tracing to, what they did at
the parent commit (2c78605)."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from granite_tiny import ROWS, TINY, apply, build, reference  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = range(len(TINY["layer_types"]))
EPS = TINY["norm_eps"]
RM = TINY["residual_multiplier"]


@pytest.fixture(scope="module")
def tiny():
    module, params = build()
    return module, params, reference.forward(params, ROWS, TINY,
                                             keep_blocks=True)


def near(got, want, tol=1e-5):
    return np.linalg.norm(np.asarray(got, np.float64) - want) \
        < tol * np.linalg.norm(want)


def f64(a):
    return np.asarray(a, np.float64)


def norm(x, gain):
    x = f64(x)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * f64(gain)


def silu(x):
    return x / (1.0 + np.exp(-f64(x)))


def operator_input(module, params, i):
    """The normed input of layer i's operator, row 0."""
    if i:
        x = apply(module, params, ROWS, capture=f"block_{i - 1}")[0]
    else:
        x = 12.0 * f64(params["embed"])[ROWS[0]]
    return norm(x, params[f"layer_{i}_operator_norm"])


def scan_inputs(key, length, heads=4, width=8, state=16, groups=2):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (2, length, heads, width))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, length, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0,
                                    maxval=2.7))
    b = jax.random.normal(ks[3], (2, length, groups, state))
    c = jax.random.normal(ks[4], (2, length, groups, state))
    return x, dt, a, b, c


# ------------------------------------------------ the family, whole

def test_registry_builds_the_family_with_its_new_keys():
    from mmlspark_tpu.models.hybrid_moe_lm import (
        OPERATORS, HybridMoEConfig, HybridMoELM)
    from mmlspark_tpu.models.networks import build_network
    assert OPERATORS == ("conv", "full_attention", "sliding_attention",
                         "mamba")
    module = build_network({"dtype": "bfloat16", **TINY})
    assert isinstance(module, HybridMoELM)
    cfg = module.cfg
    assert (cfg.attention_qk_norm, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (False, 0.0625, 12.0, 0.22, 8.0)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_expand,
            cfg.mamba_chunk_size, cfg.mamba_conv_bias,
            cfg.mamba_proj_bias) == (8, 16, 16, 1, 4, 2, 8, True, False)
    # every new key defaults to what LFM2, Mellum2 and Trinity are
    old = HybridMoEConfig()
    assert (old.attention_qk_norm, old.attention_multiplier,
            old.embedding_multiplier, old.residual_multiplier,
            old.logits_scaling) == (True, None, None, 1.0, 1.0)
    assert old.mamba_n_heads is None and old.mamba_chunk_size is None
    assert module.feature_layers() == (
        [f"block_{i}" for i in LAYERS] + [f"operator_{i}" for i in LAYERS]
        + ["final"])
    assert hash(module) == hash(build_network({"dtype": "bfloat16", **TINY}))
    with pytest.raises(ValueError):         # heads x width != inner width
        build_network({**TINY, "mamba_n_heads": 6})
    with pytest.raises(ValueError):         # heads in whole groups
        build_network({**TINY, "mamba_n_groups": 3})
    with pytest.raises(ValueError):
        build_network({**TINY, "layer_types": ["mamba", "ssm"]})


def test_the_parameter_tree(tiny):
    _, params, _ = tiny
    assert params["embed"].shape == (128, 64) and "lm_head" not in params
    for i in (0, 1, 3):
        mixer = params[f"layer_{i}_mamba"]
        assert {k: v.shape for k, v in mixer.items()} == {
            "in_proj": (64, 128 + 160 + 8), "conv": (4, 160),
            "conv_bias": (160,), "dt_bias": (8,), "A_log": (8,),
            "D": (8,), "norm": (128,), "out_proj": (128, 64)}
    # no q/k norms on the attention layer
    assert sorted(params["layer_2_attn"]) == ["k_proj", "out_proj",
                                              "q_proj", "v_proj"]
    # a dense feed-forward in every layer, and two norms
    for i in LAYERS:
        assert params[f"layer_{i}_mlp"]["gate"].shape == (64, 96)
        assert not any(k.startswith(f"layer_{i}_moe") for k in params)
        assert params[f"layer_{i}_ffn_norm"].shape == (64,)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import flops_granite
    assert count == flops_granite.parameters(TINY)


@pytest.mark.parametrize("change", [
    {"mamba_proj_bias": True}, {"mamba_conv_bias": False},
    *({key: None} for key in (
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
        "mamba_d_conv", "mamba_expand", "mamba_chunk_size"))],
    ids=lambda change: "-".join(f"{k}={v}" for k, v in change.items()))
def test_a_mixer_that_is_not_built_is_refused(change):
    """Biases other than the published ones are refused, and a mamba
    layer names every size of its mixer (none is taken from Granite)."""
    from mmlspark_tpu.models.networks import build_network
    with pytest.raises(ValueError):
        build_network({**TINY, **change})


def test_the_initial_draws():
    _, params = build(gains=False)
    mixer = params["layer_0_mamba"]
    a = np.exp(f64(mixer["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(f64(mixer["dt_bias"])))      # softplus
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert (f64(mixer["D"]) == 1).all() and (f64(mixer["norm"]) == 1).all()
    assert np.abs(f64(mixer["conv_bias"])).max() <= 0.5
    assert np.std(f64(params["embed"])) == pytest.approx(1 / 8, rel=0.05)


def test_logits_match_the_reference(tiny):
    module, params, ref = tiny
    got = apply(module, params, ROWS)
    assert got.shape == (3, 128) and got.dtype == np.float32
    assert near(got, ref["logits"])


@pytest.mark.parametrize("i", LAYERS)
def test_each_block_and_operator_match_the_reference(tiny, i):
    module, params, ref = tiny
    for name, theirs in ((f"block_{i}", ref["blocks"][i]),
                         (f"operator_{i}", ref["operators"][i])):
        got = apply(module, params, ROWS, capture=name)
        assert got.shape == (3, 37, 64)
        assert near(got, theirs), name


def test_bfloat16_stays_near_the_reference(tiny):
    _, params, _ = tiny
    module, _ = build("bfloat16")
    cast = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    got = apply(module, cast, ROWS)
    ref = reference.forward(cast, ROWS, TINY, keep_blocks=[0, 2])
    assert got.dtype == np.float32
    assert near(got, ref["logits"], 0.03)
    for i in (0, 2):
        assert near(apply(module, cast, ROWS, capture=f"operator_{i}"),
                    ref["operators"][i], 0.03), i


# ------------------------------------------------------- the chunked scan

@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_the_chunked_scan_is_the_recurrence(chunk):
    """37 positions (no multiple of 4 or 8; less than 64), two groups of
    two heads: y and the final state against the reference's recurrence
    and the program's own, and the D skip."""
    from mmlspark_tpu.ops.ssd_scan import recurrence, ssd_scan
    x, dt, a, b, c = scan_inputs(0, 37)
    d = jnp.asarray([1.0, 0.5, -0.3, 2.0])
    y, final = ssd_scan(x, dt, a, b, c, chunk, d)
    assert y.shape == x.shape and final.shape == (2, 4, 8, 16)
    assert y.dtype == final.dtype == jnp.float32
    serves = np.arange(4) // 2
    for r in range(2):
        want, state = reference.recurrence(x[r], dt[r], a, b[r][:, serves],
                                           c[r][:, serves])
        want = want + d[:, None] * x[r]
        np.testing.assert_allclose(y[r], want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(final[r], state, rtol=2e-4, atol=2e-4)
    own, own_final = recurrence(x, dt, a, b, c, d)
    np.testing.assert_allclose(y, own, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(final, own_final, rtol=2e-4, atol=2e-4)
    plain, _ = ssd_scan(x, dt, a, b, c, chunk)
    np.testing.assert_allclose(y - plain, d[:, None] * x, rtol=1e-5,
                               atol=1e-5)


def test_the_cell_s_chunk_against_a_smaller_one_and_the_recurrence():
    """At 600 positions (two chunks of 256 and a padded third; ten of
    64): chunk 256 and chunk 64 give the same y and final state, each the
    recurrence's, to rounding."""
    from mmlspark_tpu.ops.ssd_scan import ssd_scan
    x, dt, a, b, c = scan_inputs(1, 600, heads=2, state=8, groups=1)
    y256, s256 = ssd_scan(x, dt, a, b, c, 256)
    y64, s64 = ssd_scan(x, dt, a, b, c, 64)
    np.testing.assert_allclose(y256, y64, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(s256, s64, rtol=5e-4, atol=5e-4)
    for r in range(2):
        want, state = reference.recurrence(x[r], dt[r], a,
                                           jnp.repeat(b[r], 2, 1),
                                           jnp.repeat(c[r], 2, 1))
        np.testing.assert_allclose(y256[r], want, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(s256[r], state, rtol=5e-4, atol=5e-4)
    # a state carried across the boundaries is part of the answer
    cut, _ = reference.recurrence(x[0], dt[0], a, jnp.repeat(b[0], 2, 1),
                                  jnp.repeat(c[0], 2, 1), chunk=256)
    assert not np.allclose(cut[300:], y256[0, 300:], atol=1e-2)
    np.testing.assert_allclose(cut[:256], y256[0, :256], rtol=5e-4,
                               atol=5e-4)


def test_segment_sums():
    from mmlspark_tpu.ops.ssd_scan import segment_sums
    a = jnp.asarray([0.5, -1.0, 2.0, 0.25])
    got = np.asarray(segment_sums(a))
    for t in range(4):
        for s in range(4):
            want = float(np.sum(np.asarray(a)[s + 1:t + 1])) if t >= s \
                else -np.inf
            assert got[t, s] == pytest.approx(want), (t, s)


# ------------------------------------------- each new piece, by hand

def test_causality(tiny):
    """Changing token t + 1 leaves every output at positions up to t bit
    for bit, through every layer; changing token t moves position t."""
    module, params, _ = tiny
    t = 20
    rows = ROWS.copy()
    rows[:, t + 1] = (rows[:, t + 1] + 1) % 128
    for name in ("operator_0", "block_1", "operator_2", "block_3"):
        base = apply(module, params, ROWS, capture=name)
        moved = apply(module, params, rows, capture=name)
        assert np.array_equal(moved[:, :t + 1], base[:, :t + 1]), name
        assert not np.array_equal(moved[:, t + 1], base[:, t + 1]), name
    # the carried state reaches every later chunk: token 0 moves the
    # last position of layer 0's operator (chunk 4 of 5)
    rows = ROWS.copy()
    rows[:, 0] = (rows[:, 0] + 1) % 128
    base = apply(module, params, ROWS, capture="operator_0")
    assert not np.array_equal(
        apply(module, params, rows, capture="operator_0")[:, -1],
        base[:, -1])


def mixer_by_hand(p, u, gated_norm="before"):
    """The Mamba-2 operator of the tiny preset in float64, step by step:
    u (l, d) normed -> (conv output, y before the gated norm, Op)."""
    p = jax.tree_util.tree_map(f64, p)
    length = u.shape[0]
    zxd = u @ p["in_proj"]
    z, xbc, dt = zxd[:, :128], zxd[:, 128:288], zxd[:, 288:]
    past = np.concatenate([np.zeros((3, 160)), xbc])
    conv = sum(p["conv"][j] * past[j:j + length] for j in range(4))
    xbc = silu(conv + p["conv_bias"])
    x = xbc[:, :128].reshape(length, 8, 16)
    b, c = xbc[:, 128:144], xbc[:, 144:]
    step = np.log1p(np.exp(dt + p["dt_bias"]))
    a = -np.exp(p["A_log"])
    s = np.zeros((8, 16, 16))
    y = np.zeros((length, 8, 16))
    for t in range(length):
        s = np.exp(step[t] * a)[:, None, None] * s \
            + (step[t][:, None] * x[t])[:, :, None] * b[t][None, None, :]
        y[t] = s @ c[t] + p["D"][:, None] * x[t]
    y = y.reshape(length, 128)
    gated = norm(y * silu(z), p["norm"]) if gated_norm == "before" \
        else norm(y, p["norm"]) * silu(z)
    return xbc, y, gated @ p["out_proj"]


def test_the_mixer_by_hand_and_its_conv(tiny):
    """Layer 0's operator written out in float64 (the recurrence a
    position at a time); the conv is ``short_conv`` (LFM2's, the last
    tap on the present) plus the bias and a silu."""
    from mmlspark_tpu.models.hybrid_moe_lm import short_conv
    module, params, _ = tiny
    p = params["layer_0_mamba"]
    u = operator_input(module, params, 0)
    xbc, _, want = mixer_by_hand(p, u)
    got = apply(module, params, ROWS, capture="operator_0")[0]
    assert near(got, want, 2e-5)
    zxd = jnp.asarray(u, jnp.float32) @ p["in_proj"]
    conv = short_conv(zxd[None, :, 128:288], p["conv"])[0]
    np.testing.assert_allclose(jax.nn.silu(conv + p["conv_bias"]), xbc,
                               rtol=1e-5, atol=1e-5)
    # the taps the other way round are another model
    reversed_taps = {**p, "conv": p["conv"][::-1]}
    assert not near(mixer_by_hand(reversed_taps, u)[2], want, 0.05)


def test_the_gated_norm_s_order(tiny):
    """y * silu(z) goes into the norm: the other order, N(y) * silu(z),
    is another model, and the reference's control says the same."""
    module, params, ref = tiny
    u = operator_input(module, params, 0)
    _, _, want = mixer_by_hand(params["layer_0_mamba"], u)
    _, _, after = mixer_by_hand(params["layer_0_mamba"], u, "after")
    got = apply(module, params, ROWS, capture="operator_0")[0]
    assert near(got, want, 2e-5) and not near(got, after, 0.1)
    wrong = reference.forward(params, ROWS, TINY, keep_blocks=[0],
                              gated_norm="after")
    assert near(wrong["operators"][0][0], after, 2e-5)


def test_attention_with_no_norms_no_rotary_step_and_its_own_scale(tiny):
    """Layer 2's operator by hand: q, k, v with no norm and no turn, the
    scores times 1/16 (the tiny head's 1 / head_dim), key/value head
    h // 2 for query head h. The multiplier is folded into q as 1/16 x
    sqrt(16) = 1/4, exact in bfloat16, and the traced operator holds no
    cos, no sin and no rsqrt."""
    from mmlspark_tpu.models.hybrid_moe_lm import (
        GroupedQueryAttention, HybridMoEConfig)
    module, params, _ = tiny
    p = jax.tree_util.tree_map(f64, params["layer_2_attn"])
    u = operator_input(module, params, 2)
    q = np.einsum("ld,dhk->lhk", u, p["q_proj"])
    k = np.repeat(np.einsum("ld,dhk->lhk", u, p["k_proj"]), 2, 1)
    v = np.repeat(np.einsum("ld,dhk->lhk", u, p["v_proj"]), 2, 1)

    def attend(scale):
        s = np.einsum("qhd,khd->hqk", q, k) * scale
        s = np.where(np.tril(np.ones((37, 37), bool)), s, -np.inf)
        prob = np.exp(s - s.max(-1, keepdims=True))
        o = np.einsum("hqk,khd->qhd", prob / prob.sum(-1, keepdims=True), v)
        return np.einsum("lhk,hkd->ld", o, p["out_proj"])
    got = apply(module, params, ROWS, capture="operator_2")[0]
    assert near(got, attend(1 / 16), 2e-5)
    assert not near(got, attend(1 / 4), 0.05)          # 1 / sqrt(16)
    cfg = HybridMoEConfig(**{k: v for k, v in TINY.items() if k != "type"},
                          dtype=jnp.bfloat16)
    layer = GroupedQueryAttention(cfg, "full_attention")
    x = jnp.zeros((1, 37, 64), jnp.bfloat16)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    text = str(jax.make_jaxpr(layer.apply)(shapes, x))
    assert " cos " not in text and " sin " not in text
    assert "rsqrt" not in text
    assert "0.25" in text
    # folded into q before its cast: exact, so q * 1/4 in bfloat16 is
    # the float32 product's rounding
    qf = jax.random.normal(jax.random.PRNGKey(3), (64,)) * 30
    assert (jnp.asarray(qf * 0.25).astype(jnp.bfloat16)
            == jnp.asarray(qf).astype(jnp.bfloat16) * 0.25).all()
    # the keys off: norms and 1 / sqrt(d), as the older configurations
    normed = HybridMoEConfig(**{k: v for k, v in TINY.items()
                                if k not in ("type", "attention_qk_norm",
                                             "attention_multiplier")})
    shapes = jax.eval_shape(GroupedQueryAttention(normed).init,
                            jax.random.PRNGKey(0), x)
    assert sorted(shapes["params"]) == ["k_layernorm", "k_proj",
                                        "out_proj", "q_layernorm",
                                        "q_proj", "v_proj"]


def test_the_three_multipliers(tiny):
    """x0 = 12 E[t]; h = x + 0.22 Op(N(x)); x' = h + 0.22 MLP(N(h));
    logits = E N(x[last]) / 8: layer 0 by hand from its captured
    operator, the head from the last block; each multiplier at 1 is
    another model."""
    module, params, _ = tiny
    x0 = 12.0 * f64(params["embed"])[ROWS[0]]
    a = apply(module, params, ROWS, capture="operator_0")[0]
    h = x0 + RM * a
    mlp = jax.tree_util.tree_map(f64, params["layer_0_mlp"])
    u = norm(h, params["layer_0_ffn_norm"])
    y = (silu(u @ mlp["gate"]) * (u @ mlp["up"])) @ mlp["down"]
    got = apply(module, params, ROWS, capture="block_0")[0]
    assert near(got, h + RM * y, 2e-5)
    assert not near(got, x0 + a + y, 0.1)
    last = apply(module, params, ROWS, capture="block_3")[:, -1]
    head = norm(last, params["embedding_norm"]) @ f64(params["embed"]).T
    logits = apply(module, params, ROWS)
    assert near(logits, head / 8, 2e-5) and not near(logits, head, 0.5)
    for off in ({"residual_multiplier": 1.0},
                {"embedding_multiplier": None}, {"logits_scaling": 1.0}):
        other, _ = build(**off)
        assert not near(apply(other, params, ROWS), logits, 0.05), off
    for control in ({"residual_multiplier": False},
                    {"embedding_multiplier": False},
                    {"logits_scaling": False}):
        wrong = reference.forward(params, ROWS, TINY, **control)
        assert not near(wrong["logits"], logits, 0.05), control


def test_a_family_with_no_expert_layer(tiny):
    """num_dense_layers the depth and no expert: no ExpertLayer is built,
    nothing is routed, and the row stats read 0."""
    from mmlspark_tpu.models.expert_layer import _pass_rows
    module, params, _ = tiny
    assert not any("moe" in k or "router" in k for k in params)
    assert _pass_rows(3 * 37 * 0, 0, 0) == 0
    _, sown = jax.jit(lambda p, t: module.apply(
        {"params": p}, t, mutable=["stats"]))(params, jnp.asarray(ROWS))
    stats = sown["stats"]
    for name in module.row_stats:
        assert np.asarray(stats[name][-1]).tolist() == [0.0] * 3, name
    assert "routed_tail" not in stats
    assert np.asarray(stats["ssm_tail"][-1]).shape == (3, 3, 4, 64)
    assert np.asarray(stats["attention_tail"][-1]).shape == (3, 1, 4, 64)
    assert (module.moe_gather_combines, module.moe_shared_experts) == (0, 0)


# ------------------------------------------------------ the normal path

def test_through_tpu_model_with_its_counters_and_ssm_tail(tiny):
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    module, params, ref = tiny
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=2)
    model.set("fetchDict", {"scores": "output", "ssm_tail": "ssm_tail",
                            "attention_tail": "attention_tail"})
    out = model.transform(DataTable({"features": ROWS.astype(np.float32)}))
    assert near(out["scores"], ref["logits"])
    mixed = np.asarray(out["ssm_tail"])
    assert mixed.shape == (3, 3, 4, 64)
    for j, i in enumerate((0, 1, 3)):       # every Mamba-2 operator's
        captured = apply(module, params, ROWS, capture=f"operator_{i}")
        np.testing.assert_allclose(mixed[:, j], captured[:, -4:],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["attention_tail"])[:, 0],
                               ref["operators"][2][:, -4:], rtol=1e-3,
                               atol=1e-5)
    m = model.metrics()
    # 3 layers; ceil(37 / 8) = 5 chunks; a layer's state 8 x 16 x 16 and
    # conv tail 3 x 160, float32
    assert (m["ssm_layers"], m["ssm_chunks"]) == (3, 5)
    assert m["ssm_state_bytes"] == 3 * 4 * (8 * 16 * 16 + 3 * 160) == 30336
    assert (m["rope_free_layers"], m["moe_gather_combines"]) == (1, 0)
    r = PromRenderer()
    pipeline_families(r, model, {})
    text = r.render()
    assert "serving_model_ssm_layers 3" in text
    assert "serving_model_ssm_chunks 5" in text
    assert "serving_model_ssm_state_bytes 30336" in text
    # a module without the three reads 0 and exports it
    from trinity_tiny import build as build_trinity
    other, other_params = build_trinity()
    plain = TPUModel.from_flax(other, {"params": other_params},
                               inputCol="features", outputCol="scores",
                               batchSize=2)
    m = plain.metrics()
    assert (m["ssm_layers"], m["ssm_chunks"], m["ssm_state_bytes"]) == (
        0, 0, 0)


def test_the_cell_s_counters():
    from mmlspark_tpu.models.networks import build_network
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "configs",
        "granite-4.0-h-micro.json")))["networkSpec"]
    module = build_network({"dtype": "bfloat16", **spec})
    assert (module.ssm_layers, module.ssm_chunks) == (36, 4)
    # float32 states 64 x 64 x 128 and conv tails 3 x 4352 a layer
    assert module.ssm_state_bytes == 36 * 4 * (64 * 64 * 128 + 3 * 4352) \
        == 77_377_536
    assert (module.rope_free_layers, module.attn_gated_layers,
            module.moe_gather_combines) == (4, 0, 0)


# --------------------------- what the other configurations build and trace

# sha256 over the sorted "path:shape:dtype" lines of the parameter tree
# that each configuration's networkSpec built at commit 2c78605, and of
# the whole step's jaxpr over bfloat16 parameters and these token rows
_PARENT = {
    "trinity-mini-stage": (
        93, "5324317a035a58e947a186a764907af829dc530dbdb5920417276ff0f4780350",
        (2, 2048),
        "eedae1d31339b9c3224dfd2d35e0e3b4efceb65e0ebdf42e64762f481e324a2f"),
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
    assert not any("mamba" in line for line in lines)
    text = str(jax.make_jaxpr(
        lambda v, t: module.apply(v, t, mutable=["stats"]))(
        variables, jax.ShapeDtypeStruct(rows, jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() == step_digest
    assert (getattr(module, "ssm_layers", 0),
            getattr(module, "ssm_state_bytes", 0)) == (0, 0)
