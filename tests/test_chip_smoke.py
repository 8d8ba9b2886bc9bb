"""chip_smoke.py's legs at tiny sizes on the CPU (kernels interpreted
where the code has a route to them), and its refusal to run without a
chip. The real check is ``python chip_smoke.py`` on a TPU."""

import numpy as np
import pytest

import chip_smoke

TINY = {
    **chip_smoke.FULL,
    "lm_spec": {"type": "transformer", "vocab_size": 32, "dim": 16,
                "depth": 1, "heads": 2, "max_len": 8,
                "head_dtype": "bfloat16"},
    "lm_steps_per_epoch": 1,
    "transform_rows": 1,
    "serve_classes": 4,
    "serve_requests": 2,
    "gbdt_rows": 1024,
    "gbdt_valid_rows": 512,
    "gbdt_features": 4,
    "gbdt_leaves": 4,
    "gbdt_iterations": 2,
    "gbdt_max_bins": (255,),
    "gbdt_hist_method": "pallas",     # interpreted off the chip
    "gbdt_min_auc": 0.6,
    "pipeline_rows": 200,
    "gqa": {"batch": 1, "length": 48, "heads": 4, "kv_heads": 2,
            "head_dim": 16},
    "grouped": {"rows": 64, "groups": 4, "k": 16, "n": 24},
    "swa": {"batch": 1, "length": 96, "heads": 4, "kv_heads": 1,
            "head_dim": 16, "window": 20},
    "grouped_narrow": [{"rows": 64, "groups": 4, "k": 24, "n": 8},
                       {"rows": 64, "groups": 4, "k": 8, "n": 24}],
    "fused_swiglu": [{"rows": 64, "groups": 4, "k": 16, "n": 24},
                     {"rows": 64, "groups": 4, "k": 24, "n": 8},
                     {"rows": 64, "groups": 8, "k": 16, "n": 8,
                      "live": [3, 4]}],
    "combine": {"tokens": 48, "k": 4, "dim": 16, "passes": 4},
    "layer_down": [{"rows": 64, "passes": 4, "groups": 4, "k": 16, "n": 24},
                   {"rows": 96, "passes": 3, "groups": 8, "k": 8, "n": 16},
                   {"rows": 64, "passes": 4, "groups": 16, "k": 8, "n": 16}],
    "swa_wide": {"batch": 1, "length": 96, "heads": 4, "kv_heads": 1,
                 "head_dim": 16, "window": 40},
    "grouped_wide": {"rows": 64, "groups": 16, "k": 8, "n": 16},
    "ssd": {"batch": 2, "length": 40, "heads": 4, "head_dim": 8,
            "state": 16, "groups": 1, "chunk": 16},
    "gqa_scaled": {"batch": 1, "length": 48, "heads": 4, "kv_heads": 2,
                   "head_dim": 16, "multiplier": 1 / 16},
}


def test_train_then_transform():
    facts, model, toks = chip_smoke.leg_train(TINY, 1)
    assert facts["steps"] == 2
    facts = chip_smoke.leg_transform(TINY, model, toks, 1)
    assert facts["rel_l2_vs_f32"] < chip_smoke.BF16_REL_TOL


def test_serve():
    facts = chip_smoke.leg_serve(TINY)
    assert facts["requests"] == facts["predictions_equal_reference"] == 2


def test_kernels_leg():
    facts = chip_smoke.leg_kernels(TINY)
    assert facts["gqa_rel_l2_vs_f32"] < chip_smoke.BF16_REL_TOL
    assert facts["grouped_rel_l2"] < chip_smoke.BF16_REL_TOL
    assert facts["grouped_tiles_k_n"] == [16, 24]
    full = chip_smoke.FULL["grouped"]
    assert (full["k"], full["n"]) == (2048, 1536)
    assert facts["swa_rel_l2_vs_f32"] < chip_smoke.BF16_REL_TOL
    assert max(facts["grouped_narrow_rel_l2"]) < chip_smoke.BF16_REL_TOL
    assert facts["grouped_narrow_tiles_k_n"] == [[24, 8], [8, 24]]
    full = chip_smoke.FULL["swa"]
    assert full["length"] // 1024 == 16 and full["window"] == 1024
    assert (full["heads"], full["kv_heads"], full["head_dim"]) == (32, 4, 128)
    from mmlspark_tpu.ops.grouped_matmul import _tile
    assert [[_tile(m["k"], 1024), _tile(m["n"], 1024)]
            for m in chip_smoke.FULL["grouped_narrow"]] == [
        [768, 896], [896, 768]]
    for into_f32, into_bf16 in facts["fused_swiglu_rel_l2"]:
        assert into_f32 < 1e-5 and into_bf16 < chip_smoke.BF16_REL_TOL
    # the rows read through their ids are the gathered rows, to the bit
    assert facts["fused_swiglu_ids_differ"] == [0, 0, 0]
    assert [(m["rows"], m["groups"], m["k"], m["n"])
            for m in chip_smoke.FULL["fused_swiglu"]] == [
        (4096, 8, 2048, 1536), (4096, 8, 2304, 896),
        (32768, 128, 2048, 1024)]
    # Trinity-Mini's pass: its rows fall to 16 of the 128 experts
    sizes = chip_smoke._uneven_sizes(chip_smoke.FULL["fused_swiglu"][2])
    assert (np.flatnonzero(sizes).min(), np.flatnonzero(sizes).max(),
            (sizes[48:64] > 0).sum()) == (49, 62, 10)
    assert 32768 - 8 - 16 < sizes.sum() <= 32768 - 8
    assert facts["combine_rel_l2_vs_scatter_add"] < 1e-6
    full = chip_smoke.FULL["combine"]
    assert (full["tokens"] * full["k"], full["dim"]) == (131072, 2048)
    assert facts["layer_down_rel_l2"] == [0.0, 0.0, 0.0]
    assert [(m["rows"], m["k"], m["n"], m["rows"] // m["passes"])
            for m in chip_smoke.FULL["layer_down"]] == [
        (131072, 1536, 2048, 32768), (262144, 896, 2304, 32768),
        (262144, 1024, 2048, 32768)]
    # a window of 2048 at 16,384 tokens, and the down product over 128
    assert facts["swa_wide_rel_l2_vs_f32"] < chip_smoke.BF16_REL_TOL
    assert facts["grouped_wide_rel_l2"] < chip_smoke.BF16_REL_TOL
    wide = chip_smoke.FULL["swa_wide"]
    assert (wide["length"], wide["window"], wide["heads"],
            wide["kv_heads"], wide["head_dim"]) == (16384, 2048, 32, 4, 128)
    assert chip_smoke.FULL["grouped_wide"] == {
        "rows": 262144, "groups": 128, "k": 1024, "n": 2048}
    # Granite-4.0-H-Micro's scan against the recurrence, and its flash
    # call with the scale folded into q
    assert max(facts["ssd_rel_l2_vs_recurrence"]) < chip_smoke.BF16_REL_TOL
    # off a TPU ``ssd_scan`` is its own ``jax.numpy`` steps
    assert facts["ssd_kernel_rel_l2_vs_jnp"] == [0.0, 0.0]
    assert facts["ssd_kernel_runs"] is False
    assert facts["gqa_scaled_rel_l2_vs_f32"] < chip_smoke.BF16_REL_TOL
    assert chip_smoke.FULL["ssd"] == {
        "batch": 8, "length": 1024, "heads": 64, "head_dim": 64,
        "state": 128, "groups": 1, "chunk": 256}
    scaled = chip_smoke.FULL["gqa_scaled"]
    assert scaled["multiplier"] * scaled["head_dim"] ** 0.5 == 0.125


def test_gbdt_and_fused_pipeline():
    facts = chip_smoke.leg_gbdt(TINY, 1)
    assert facts["pipeline"]["roundtrips"] == 1
    assert "TPUBoost" in facts["pipeline"]["plan"]


def test_unbalanced_placement_is_caught():
    chip_smoke.assert_balanced([100, 60, 80, 100], "even")
    with pytest.raises(AssertionError, match="peaks"):
        chip_smoke.assert_balanced([100, 0, 0, 0], "device 0 only")


def test_main_refuses_a_cpu_backend(capsys):
    """No CPU mode: leg 1 exits non-zero and no result line appears."""
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main()
    assert "needs a TPU" in str(exit_info.value.code)   # exit status 1
    assert '"ok"' not in capsys.readouterr().out
