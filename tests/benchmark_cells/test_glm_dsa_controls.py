"""The controls of ``correct`` for ``glm52_score_8k_steady`` at the tiny
preset on the CPU: the program reads correct, and the reference with
one thing changed (``control_glm_dsa.STAND_INS``) in its place does
not, each by the number that is its own."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "glm_dsa_cell_test_for_controls",
    os.path.join(_HERE, "test_glm_dsa_cell.py"))
cell_test = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cell_test)


def test_controls_read_not_correct(tmp_path):
    serve_lm = cell_test.run.load_module(os.path.join(cell_test.BENCH_DIR, "drivers",
                                            "serve_lm.py"))
    import control_glm_dsa
    assert set(control_glm_dsa.STAND_INS) == {
        "fp8", "full_causal", "no_routed", "layer2_sets"}
    root = cell_test.make_root(tmp_path)
    cell = cell_test.run.load_cell(root, cell_test.CELL)
    cell["seconds"] = 1.0
    got = serve_lm.control(cell, 17, ["sound", *control_glm_dsa.STAND_INS])
    got.pop("info")
    value = {name: {c["name"]: c["value"] for c in checks}
             for name, checks in got.items()}
    assert cell_test.run.judge(got["sound"])
    for name in control_glm_dsa.STAND_INS:
        assert not cell_test.run.judge(got[name]), (name, value[name])
    # each by the number that is its own
    assert value["layer2_sets"]["select_miss"] > 0.3
    assert value["full_causal"]["logit_rel_l2"] > \
        2 * value["sound"]["logit_rel_l2"]
    assert value["no_routed"]["logit_rel_l2"] > \
        2 * value["sound"]["logit_rel_l2"]
    assert value["fp8"]["logit_rel_l2"] > 2 * value["sound"]["logit_rel_l2"]
