"""``hybrid_moe_lm`` on the CPU at a tiny size: the family against the
plain reference (logits and every captured block), the gated short
convolution against an explicit loop over t and its causality, an expert
layer that holds every expert against the plain sum, the routed experts
under a ceiling of rows a pass, and the model on the normal path
(``TPUModel.transform`` with its per-row counters)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from hybrid_moe_tiny import ROWS, TINY, apply, build, reference  # noqa: E402

LAYERS = range(len(TINY["layer_types"]))


@pytest.fixture(scope="module")
def tiny():
    module, params = build()
    return module, params, reference.forward(params, ROWS, TINY,
                                             keep_blocks=True)


def test_registry_builds_the_family():
    from mmlspark_tpu.models.hybrid_moe_lm import HybridMoELM
    from mmlspark_tpu.models.networks import NETWORK_REGISTRY, build_network
    assert "hybrid_moe_lm" in NETWORK_REGISTRY
    module = build_network({"dtype": "bfloat16", **TINY})
    assert isinstance(module, HybridMoELM) and module.int_input
    assert module.cfg.experts_held == module.cfg.experts_total == 16
    assert module.cfg.head_dim == 8 and module.cfg.n_shared_experts == 0
    assert module.row_stats == ("moe_tokens_held", "moe_load_max_over_mean",
                                "moe_passes")
    assert module.feature_layers() == (
        [f"block_{i}" for i in LAYERS] + [f"operator_{i}" for i in LAYERS]
        + [f"routed_{i}" for i in (1, 2, 3, 4)] + ["final"])


@pytest.mark.parametrize("bad", [
    {"layer_types": ["conv", "window"]}, {"num_key_value_heads": 3}])
def test_a_spec_that_makes_no_model_is_refused(bad):
    from mmlspark_tpu.models.networks import build_network
    with pytest.raises(ValueError):
        build_network({**TINY, **bad})


def test_logits_match_the_reference(tiny):
    module, params, ref = tiny
    got = apply(module, params, ROWS)
    assert got.shape == (3, 128) and got.dtype == np.float32
    assert np.linalg.norm(got - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])


@pytest.mark.parametrize("i", LAYERS)
def test_each_block_and_operator_match_the_reference(tiny, i):
    module, params, ref = tiny
    for name, want in (("block", ref["blocks"][i]),
                       ("operator", ref["operators"][i])):
        got = apply(module, params, ROWS, capture=f"{name}_{i}")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"{name}_{i}")


@pytest.mark.parametrize("i", [1, 2, 3, 4])
def test_each_expert_layer_chooses_the_reference_s_experts(tiny, i):
    module, params, ref = tiny
    got = apply(module, params, ROWS, capture=f"routed_{i}")
    assert got.shape == (3, 32, 4)
    # float32 both sides: the same four, but for a tie under 1e-6
    same = (np.sort(got, -1) == np.sort(ref["routed"][i], -1)).all(-1)
    assert (same | (ref["router_margin"][i] < 1e-6)).all()
    assert (ref["router_margin"][i] >= 0).all()


def test_bfloat16_stays_near_the_reference(tiny):
    _, params, ref = tiny
    module, _ = build("bfloat16")
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    got = apply(module, low, ROWS)
    rel = np.linalg.norm(got - ref["logits"], axis=-1) \
        / np.linalg.norm(ref["logits"], axis=-1)
    # a flipped expert at 64 wide is a tenth of a logit's size
    assert np.median(rel) < 0.05 and rel.max() < 0.5


# ------------------------------------------------ the gated short convolution

def test_short_conv_equals_a_loop_over_t():
    from mmlspark_tpu.models.hybrid_moe_lm import short_conv
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, 9, 5)))
    taps = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (3, 5)))
    want = np.zeros_like(g)
    for t in range(9):
        for j in range(3):              # taps[2] weighs the present
            if t - (2 - j) >= 0:
                want[:, t] += taps[j] * g[:, t - (2 - j)]
    np.testing.assert_allclose(short_conv(jnp.asarray(g), jnp.asarray(taps)),
                               want, rtol=1e-6, atol=1e-6)


def test_conv_operator_matches_the_reference_s_and_is_causal():
    from mmlspark_tpu.models.hybrid_moe_lm import HybridMoEConfig, ShortConv
    cfg = HybridMoEConfig(**{k: v for k, v in TINY.items() if k != "type"},
                          dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 64), jnp.float32)
    op = ShortConv(cfg)
    params = op.init(jax.random.PRNGKey(4), u)["params"]
    assert {k: v.shape for k, v in params.items()} == {
        "in_proj": (64, 192), "conv": (3, 64), "out_proj": (64, 64)}
    got = np.asarray(op.apply({"params": params}, u))
    for r in range(2):
        np.testing.assert_allclose(
            got[r], reference.conv_operator(params, u[r]),
            rtol=1e-4, atol=1e-5)
    # changing token t + 1 leaves the outputs up to t bit for bit
    t = 6
    moved = u.at[:, t + 1:].add(1.0)
    again = np.asarray(op.apply({"params": params}, moved))
    assert (again[:, :t + 1] == got[:, :t + 1]).all()
    assert np.abs(again[:, t + 1] - got[:, t + 1]).max() > 1e-3
    # the two past taps matter: dropping them is another operator
    none = reference.conv_operator(params, u[0], past_taps=False)
    assert np.abs(np.asarray(none) - got[0]).max() > 1e-2


def test_the_model_is_causal_up_to_its_last_position():
    module, params = build()
    moved = ROWS.copy()
    moved[:, -1] = (moved[:, -1] + 1) % 128
    a = apply(module, params, ROWS, capture="block_4")
    b = apply(module, params, moved, capture="block_4")
    assert (a[:, :-1] == b[:, :-1]).all() and (a[:, -1] != b[:, -1]).any()


# ------------------------------------------------------------ the expert layer

def test_an_expert_layer_that_holds_every_expert_is_the_plain_sum():
    from mmlspark_tpu.models.expert_layer import ExpertLayer
    from mmlspark_tpu.models.hybrid_moe_lm import HybridMoEConfig
    cfg = HybridMoEConfig(**{k: v for k, v in TINY.items() if k != "type"},
                          dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    layer = ExpertLayer(cfg)
    params = layer.init(jax.random.PRNGKey(6), u)["params"]
    assert "shared_0" not in params             # no shared expert
    y, chosen, load = layer.apply({"params": params}, u)
    want, ref_chosen, *_ = reference.experts(params, TINY, u)
    assert (np.sort(chosen, -1) == np.sort(ref_chosen, -1)).all()
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert int(load.sum()) == 48 * 4 and load.shape == (16,)
    # ... which is the sum over all 16 experts, written out
    score = jax.nn.sigmoid(u @ params["router"].T)
    plain = np.zeros((48, 64), np.float32)
    for t in range(48):
        picked = score[t, ref_chosen[t]]
        for e, s in zip(np.asarray(ref_chosen[t]), picked):
            h = jax.nn.silu(u[t] @ params["experts_gate"][e]) \
                * (u[t] @ params["experts_up"][e])
            plain[t] += np.asarray(s / (picked.sum() + 1e-6)
                                   * (h @ params["experts_down"][e]))
    np.testing.assert_allclose(y, plain, rtol=1e-4, atol=1e-5)
    # the bias enters the choice only: without it another set is chosen
    _, no_bias, *_ = reference.experts(params, TINY, u,
                                          bias_in_choice=False)
    assert (np.sort(no_bias, -1) != np.sort(ref_chosen, -1)).any()


def test_the_gates_normaliser_is_a_spec_key():
    from mmlspark_tpu.models.expert_layer import route
    u = jax.random.normal(jax.random.PRNGKey(7), (6, 16))
    router = jax.random.normal(jax.random.PRNGKey(8), (8, 16))
    bias = jnp.zeros((8,))
    chosen, plain = route(u, router, bias, 2, 2.5)
    again, eps = route(u, router, bias, 2, 2.5, 0.5)
    assert (chosen == again).all()
    np.testing.assert_allclose(plain.sum(-1), 2.5, rtol=1e-6)
    picked = jnp.take_along_axis(jax.nn.sigmoid(u @ router.T), chosen, -1)
    np.testing.assert_allclose(eps, 2.5 * picked / (picked.sum(-1, keepdims=True)
                                                    + 0.5), rtol=1e-6)


def test_pass_rows_keep_their_size_under_the_ceiling():
    from mmlspark_tpu.models import expert_layer as el
    # GLM's cell: 4 x 8192 tokens x 8 a token, 16 of 256 held
    assert el._pass_rows(4 * 8192 * 8, 16, 256) == 20480
    # LFM2's: every pair is here; the ceiling makes it four passes
    assert el.PASS_ROWS_MAX == 32768
    assert el._pass_rows(4 * 8192 * 4, 64, 64) == 32768
    assert el._pass_rows(8192 * 4, 64, 64) == 32768
    assert el._pass_rows(64 * 4, 4, 16) == 80      # the tests' small size


def test_routed_experts_under_a_ceiling_equal_one_pass(monkeypatch):
    from mmlspark_tpu.models import expert_layer as el
    t, k, held, d, w = 512, 4, 4, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    u = jax.random.normal(keys[0], (t, d))
    chosen = jax.random.randint(keys[1], (t, k), 0, held)
    gates = jax.random.uniform(keys[2], (t, k))
    wg, wu = (jax.random.normal(kk, (held, d, w)) for kk in keys[3:5])
    wd = jax.random.normal(keys[5], (held, w, d))

    def run():
        return jax.jit(lambda *a: el.routed_experts(*a, 0, held))(
            u, chosen, gates, wg, wu, wd)
    assert el._pass_rows(t * k, held, held) == t * k        # one pass
    whole, load = run()
    monkeypatch.setattr(el, "PASS_ROWS_MAX", 512)
    assert el._pass_rows(t * k, held, held) == 512          # four passes
    capped, load_capped = run()
    np.testing.assert_allclose(capped, whole, rtol=1e-5, atol=1e-5)
    assert load.tolist() == load_capped.tolist() and int(load.sum()) == t * k


def test_both_families_import_one_expert_layer():
    from mmlspark_tpu.models import expert_layer, hybrid_moe_lm, latent_moe_lm
    for name in ("ExpertLayer", "GatedMLP", "_pass_rows", "rms_norm"):
        assert getattr(latent_moe_lm, name) is getattr(expert_layer, name)
        assert getattr(hybrid_moe_lm, name) is getattr(expert_layer, name)
    assert latent_moe_lm.routed_experts is expert_layer.routed_experts
    assert latent_moe_lm.LatentMoEConfig().gate_norm_eps == 0.0


# ------------------------------------------------------------ the normal path

def test_through_tpu_model_transform_with_its_row_counters(tiny):
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    module, params, ref = tiny
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=4)
    out = model.transform(DataTable(
        {"features": ROWS.astype(np.float32)}))["scores"]
    assert np.linalg.norm(out - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])
    hists = model.histograms()
    # one entry a real row: the bucket's padded fourth row is left out
    for name in module.row_stats:
        assert hists[name].snapshot()["count"] == 3, name
    # every routed pair is held: 32 tokens x 4 experts x 4 layers a row
    assert hists["moe_tokens_held"].snapshot()["sum"] == 3 * 32 * 4 * 4
    assert hists["moe_passes"].snapshot()["sum"] == 3 * 1.0
    assert hists["moe_load_max_over_mean"].snapshot()["sum"] / 3 >= 1.0
    assert model.metrics()["weights_cast_leaves"] == 0
    # the experts the same execution chose at each row's last
    # positions ride out as an output that fetchDict can name, without
    # another compile
    misses = model.jit_cache_misses
    model.set("fetchDict", {"scores": "output",
                            "routed_tail": "routed_tail",
                            "attention_tail": "attention_tail"})
    both = model.transform(DataTable({"features": ROWS.astype(np.float32)}))
    assert model.jit_cache_misses == misses
    assert np.array_equal(both["scores"], out)
    tail = np.asarray(both["routed_tail"])
    assert tail.shape == (3, 4, 16, 4) and module.row_outputs == (
        "routed_tail", "attention_tail")
    for nth, i in enumerate((1, 2, 3, 4)):
        want = apply(module, params, ROWS, capture=f"routed_{i}")[:, -16:]
        assert (np.sort(tail[:, nth], -1) == np.sort(want, -1)).all()
    # ... and every attention operator's output at the last positions
    attended = np.asarray(both["attention_tail"])
    assert attended.shape == (3, 1, 4, 64)
    np.testing.assert_allclose(
        attended[:, 0], apply(module, params, ROWS,
                              capture="operator_1")[:, -4:], rtol=1e-3)
    assert not set(module.row_outputs) & set(hists)
