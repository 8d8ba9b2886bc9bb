"""The controls of ``correct`` for ``lfm2_score_8k_steady`` at the tiny
preset on the CPU: the program reads correct, and the reference with
one thing changed (``control_lfm2.STAND_INS``) in its place does not."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "lfm2_cell_test_for_controls", os.path.join(_HERE, "test_lfm2_cell.py"))
cell_test = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cell_test)


def test_controls_read_not_correct(tmp_path):
    driver = cell_test.run.load_module(os.path.join(
        cell_test.BENCH_DIR, "drivers", "serve_hybrid_lm.py"))
    import control_lfm2
    assert set(control_lfm2.STAND_INS) == {
        "fp8", "no_routed", "no_past_taps", "kv_mod", "no_qk_norm",
        "no_bias"}
    root = cell_test.make_root(tmp_path)
    cell = cell_test.run.load_cell(root, cell_test.CELL)
    cell["seconds"] = 1.0
    got = driver.control(cell, 17, ["sound", "unforced",
                                    *control_lfm2.STAND_INS])
    info = got.pop("info")
    value = {name: {c["name"]: c["value"] for c in checks}
             for name, checks in got.items()}
    assert cell_test.run.judge(got["sound"]), value["sound"]
    for name in control_lfm2.STAND_INS:
        assert not cell_test.run.judge(got[name]), (name, value[name])
        assert len(info[f"rows_rel_l2_{name}"]) == 8
    # each by the number that is its own: a choice by another rule by
    # ``route_gap`` and ``route_miss`` (over the cone its logits follow
    # the reference that took its choices), the arithmetic by
    # ``logit_rel_l2``
    assert value["no_bias"]["route_gap"] > 0.05
    assert value["no_bias"]["route_miss"] > 0.04 \
        > 4 * value["sound"]["route_miss"]
    for name in ("fp8", "no_routed", "no_past_taps", "kv_mod"):
        assert value[name]["logit_rel_l2"] > 0.1, (name, value[name])
    # the attention operators' own outputs hold what is theirs
    for name in ("fp8", "kv_mod", "no_qk_norm"):
        assert value[name]["attn_rel_l2"] > 0.1, (name, value[name])
        assert value[name]["attn_late_rel_l2"] > 0.1, (name, value[name])
    # ... and the experts come after the first of them
    for name in ("no_routed", "no_bias"):
        assert value[name]["attn_rel_l2"] < 1e-5, (name, value[name])
        assert value[name]["attn_late_rel_l2"] > 0.1, (name, value[name])
    # left to its own choices the reference reads what near ties cost
    assert max(info["rows_rel_l2_unforced"]) \
        > 2 * max(info["rows_rel_l2_sound"])
    assert 0 < info["tail_miss_unforced"] < 0.2
