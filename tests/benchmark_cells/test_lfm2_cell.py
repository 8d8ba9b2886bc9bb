"""The ``lfm2_score_8k_steady`` cell's own tests: CPU only, a tiny
preset. The configuration's entry and file (with its cuts, the widths
read by this configuration's own keys), the benchmark as it stands with
four cells, the yardstick ``flops_lfm2`` against hand counts, each new
reader by hand on a profile written by hand and silent with nothing to
read, the names the readers find things by, the driver end to end and
the controls of ``correct``.

Three of the checks here are those of benchmark tests that assert the
benchmark of PR 30 (three cells, uncut configurations) and are marked
expected failures from tests/conftest.py: see PERF.md, Open questions
0i. No topology or TPU call is made anywhere in this file.
"""

import importlib.util
import json
import os
import re
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
CELL = "lfm2_score_8k_steady"
GLM_CELL = "glm52_score_8k_steady"
CONFIG = "lfm2-24b-a2b-stage"
MIX = "poisson_steady_8k_lfm2"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_lfm2")
import flops_lfm2 as fl      # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce          # noqa: E402
import xplane_scopes         # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_METRICS = ["lfm2_forward_mfu", "lfm2_experts_roofline",
               "lfm2_flash_roofline", "moe_dispatch_share",
               "short_conv_gate_share"]
GENERIC = ["serve_queue_wait_ms", "scorer_device_wait_ms",
           "device_idle_serve", "serve_token_wait_ms",
           "serve_dispatch_wait_ms", "serve_worker_host_ms",
           "device_idle_serve_named", "moe_load_max_over_mean"]
SCOPES = ("short_conv", "short_conv_gate", "gqa_project", "gqa_attend",
          "moe_route", "moe_experts", "moe_grouped", "lm_head_last")
KINDS = ["conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv"]
TINY = {"vocab_size": 128, "max_len": 32, "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "rope_theta": 10000.0, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": 16}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def body():
    return json.load(open(os.path.join(BENCH_DIR, "configs",
                                       CONFIG + ".json")))


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


# ------------------------------------------------- BENCHMARK.json and the file

def test_config_entry_and_its_file_with_cuts():
    """``test_config_entry_and_its_file`` with ``reduced`` as it stands
    and the widths read by this configuration's own keys."""
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG for w in BENCH["workloads"])
    b = body()
    assert b["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert b["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    # the published values of what was cut stand beside the cut ones
    pub = b["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (40, 2)
    assert len(pub["layer_types"]) == 40
    assert [i for i, k in enumerate(pub["layer_types"])
            if k == "full_attention"] == list(range(2, 40, 4))
    assert (b["num_hidden_layers"], b["num_dense_layers"]) == (9, 1)
    # published layers 1-9: a dense conv layer and two whole periods
    assert b["layer_types"] == pub["layer_types"][1:10] == KINDS
    spec = b["networkSpec"]
    assert spec["type"] == "hybrid_moe_lm"
    assert spec["layer_types"] == b["layer_types"]
    assert spec["num_dense_layers"] == b["num_dense_layers"]
    assert len(spec["layer_types"]) == b["num_hidden_layers"]
    # no width is cut: the file's published keys and what is run agree
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "vocab_size", "conv_L_cache",
                "norm_eps", "routed_scaling_factor"):
        assert spec[key] == b[key], key
    assert (b["hidden_size"], b["intermediate_size"],
            b["moe_intermediate_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["num_experts"],
            b["num_experts_per_tok"], b["vocab_size"], b["conv_L_cache"]
            ) == (2048, 11776, 1536, 32, 8, 64, 4, 65536, 3)
    assert spec["rope_theta"] == b["rope_parameters"]["rope_theta"] == 1e6
    assert b["conv_bias"] is False and b["use_expert_bias"] is True
    assert b["norm_topk_prob"] is True and b["model_type"] == "lfm2_moe"
    assert b["max_position_embeddings"] == 128000
    assert "experts_held" not in spec          # every expert is here
    assert b["deployment"]["pipeline_stages"] == 5
    assert b["deployment"]["layers_a_stage"] == 8
    for key in ("tie_word_embeddings", "head_dim", "in_proj_order",
                "qk_norm", "rope", "gate_norm_eps", "max_len",
                "initial_weights"):
        assert b["assumed"][key], key
    assert any("no decode" in d for d in b["departures"])
    assert any("head" in d and "first stage" in d for d in b["departures"])
    assert b["parameters"] == fl.parameters(spec) == 5_177_950_976
    assert b["parameter_bytes"] == 2 * b["parameters"] + 2 * 8 * 64
    assert "bfloat16" in b["precision"]


def test_the_cell_and_what_it_reports():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "chips": 1, "traffic": MIX}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + NEW_METRICS)
    for m in BENCH["per_layer"]:
        # the other families' step readers stay theirs
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline"):
            assert CELL not in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC:
            assert m["workloads"][-1] == CELL
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in NEW_METRICS] == [
        ("model step", "higher"), ("kernels", "higher"),
        ("kernels", "higher"), ("experts", "lower"),
        ("model step", "lower")]
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_hybrid_lm" and mix["batch_size"] == 4
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["trace_window_s"]) == (
        5.0, 1, 4, 8, 12)
    assert isinstance(mix["arrivals"]["gap_seed"], int)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2", "route_gap",
                                  "route_miss", "attn_rel_l2",
                                  "attn_late_rel_l2", "served_not_model",
                                  "unanswered"}
    assert set(mix["limits_why"]) == set(mix["limits"])
    assert all(len(why) > 20 for why in mix["limits_why"].values())
    # every sampled row decides: none is set aside, so no margin
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.1
    # a wrong token reads hundreds: the limit is GLM's, and can fail
    assert mix["limits"]["class_gap"] == 0.05
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    # the first attention layer's number is the arithmetic alone; a
    # later one's carries the reference's own near ties before the cone
    assert 0 < mix["limits"]["attn_rel_l2"] \
        < mix["limits"]["attn_late_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


GLM_METRICS = ["glm_forward_mfu", "dsa_attend_roofline", "dsa_select_share",
               "moe_experts_roofline", "moe_load_max_over_mean"]


def test_the_glm_cell_reports_what_it_did():
    """``test_glm_dsa_cell.py::test_the_cell_and_what_it_reports`` as
    the benchmark stands, every assertion of it, changed in one place:
    ``moe_load_max_over_mean`` lists the GLM cell and then this one
    (ISSUE 34 appends it), where the original asserts the GLM cell
    alone."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == GLM_CELL)
    assert cell == {**cell, "config": "glm-5.2-ep16", "chips": 1,
                    "traffic": "poisson_steady_8k"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, GLM_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == \
        GENERIC[:-1] + GLM_METRICS
    # the GPT-2 step's readers stay GPT-2's
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline"):
            assert GLM_CELL not in m["workloads"]
        if m["name"] in GLM_METRICS:
            assert m["workloads"] == (
                [GLM_CELL, CELL] if m["name"] == "moe_load_max_over_mean"
                else [GLM_CELL])               # the one change
            assert m["moves"] == "serve_p95_ms"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_lm" and mix["batch_size"] == 4
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["reply_timeout_s"]) == (
        5.0, 1, 4, 8, 120)
    assert mix["arrivals"]["gap_seed"] == 20260930
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2",
                                  "near_tie_rows", "select_miss",
                                  "served_not_model", "unanswered"}
    assert 0 < mix["limits"]["select_miss"] < 1
    # some sampled row always decides, and a near tie is a small margin
    # two of the sampled rows at least decide
    assert 0 <= mix["limits"]["near_tie_rows"] <= mix["sample_requests"] - 2
    assert 0 < mix["near_tie_margin"] < 0.01
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]


def test_benchmark_json_is_still_well_formed():
    """``test_glm_dsa_cell.py::test_benchmark_json_is_still_well_formed``
    as the benchmark stands, every assertion of it, changed in one
    place: the list of cells has this one at its end."""
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", GLM_CELL,
                     CELL]                     # the one change
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)
    # ... and what PR 34 may not have moved
    assert [c["name"] for c in BENCH["configs"]] == [
        "gpt2-medium", "gpt2-xl", "glm-5.2-ep16", CONFIG]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 40
    # the new entries are the last of their lists
    assert [m["name"] for m in BENCH["per_layer"]][-5:] == NEW_METRICS
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"train_tokens_per_s": 0.01, "serve_p50_ms": 0.03,
                      "serve_p95_ms": 0.07, "setup_s": 0.1}


# ------------------------------------------------------- the yardstick by hand

def test_flops_by_hand_for_one_tiny_shape():
    s = {**TINY, "conv_L_cache": 3, "num_experts_per_tok": 4,
         "layer_types": ["conv", "full_attention", "conv"],
         "num_dense_layers": 1}
    length = 32
    assert fl.causal_pairs(length) == 528 and fl.sparse_layers(s) == 2
    assert fl.head_dim(s) == 8
    conv = 4 * 64 * 64 + 3 * 64
    attn = 2 * 64 * 8 * 8 + 2 * 64 * 2 * 8 + 2 * 8
    assert fl.conv_params(s) == conv and fl.attention_params(s) == attn
    expert = 3 * 64 * 32
    assert fl.expert_params(s) == expert
    assert fl.parameters(s) == (
        128 * 64 + 64 + 3 * 2 * 64 + 2 * conv + attn + 3 * 64 * 128
        + 2 * 16 * (64 + 1 + expert))
    flash = 2 * 2 * 8 * 528 * 8
    assert fl.flash_cost(s, 1, length) == {
        "flops": flash,
        "bytes": length * 8 * (2 * 8 + 2 * 2) * 2 + 4 * 8 * length}
    # a bucket of 4: four times the pairs, K and V once a key/value head
    assert fl.flash_cost(s, 4, length)["flops"] == 4 * flash
    conv_f = 2 * length * 4 * 64 * 64
    attn_f = 2 * length * (attn - 16) + flash
    dense = 2 * 3 * 64 * 128 * length
    router = 2 * length * 64 * 16
    pairs = 2 * length * 4                        # two expert layers
    assert fl.expected_pairs(s, length) == pairs
    routed = 2 * 3 * 64 * 32 * pairs
    head = 2 * 64 * 128
    want = 2 * conv_f + attn_f + dense + 2 * router + routed + head
    assert fl.forward_flops_per_row(s, length) == want
    # the program's own count of the pairs takes the expectation's place
    assert fl.forward_flops_per_row(s, length, 200.0) == \
        want - routed + 2 * 3 * 64 * 32 * 200.0
    assert fl.experts_cost(s, 40.0) == {
        "flops": 2 * 3 * 64 * 32 * 40.0,
        "bytes": (16 * expert + 40.0 * 2 * 64) * 2}


def test_flops_at_the_cell_s_size():
    """ISSUE 34's arithmetic, checked against the tree."""
    spec = body()["networkSpec"]
    assert fl.expert_params(spec) == 9_437_184
    assert fl.conv_params(spec) == 16_783_360
    assert fl.attention_params(spec) == 10_485_888
    expert_layer = 64 * (2048 + 1 + 9_437_184) + 2 * 2048
    assert expert_layer + fl.conv_params(spec) == 620_898_368     # 620.9 M
    assert expert_layer + fl.attention_params(spec) == 614_600_896     # 614.6 M
    assert 3 * 2048 * 11776 + 2 * 2048 + fl.conv_params(spec) \
        == 89_139_200                                             # 89.1 M
    assert 65536 * 2048 == 134_217_728
    assert fl.parameters(spec) == 5_177_950_976
    per_row = fl.forward_flops_per_row(spec, 8192)
    assert per_row == pytest.approx(8.968e12, rel=1e-3)           # 8.97 TFLOP
    pairs = 2 * fl.flash_cost(spec, 1, 8192)["flops"]
    assert pairs == pytest.approx(0.55e12, rel=0.01)
    products = (per_row - pairs - 2 * 2048 * 65536) / 8192
    assert products == pytest.approx(1027.6e6, rel=1e-4)
    experts = fl.gated_mlp_flops(2048, 1536, fl.expected_pairs(spec, 8192))
    assert experts / per_row == pytest.approx(0.55, abs=0.01)
    assert 7 * 2 * 8192 * 4 * 2048 ** 2 / per_row == pytest.approx(
        0.21, abs=0.01)
    assert fl.gated_mlp_flops(2048, 11776, 8192) / per_row == pytest.approx(
        0.13, abs=0.01)
    assert pairs / per_row == pytest.approx(0.06, abs=0.005)
    assert fl.expected_pairs(spec, 8192) == 262_144
    # the fallback of one period, were it ever needed
    one = {**spec, "layer_types": KINDS[:5]}
    assert fl.parameters(one) == 2_700_654_976
    assert fl.forward_flops_per_row(one, 8192) == pytest.approx(
        5.21e12, rel=2e-3)


# --------------------------------------------- the readers on a written profile

_J = "jit(tpu_model_forward)/HybridMoELM/"
OPS = {  # name -> (scope or None, [(start ms, length ms)])
    "%fusion.1 = f32[4,8192,6144] fusion(%p)": (
        _J + "layer_0_conv/short_conv/bld,de->ble/dot_general:",
        [(10.0, 2.0), (40.0, 2.0)]),
    "%fusion.2 = bf16[4,8192,2048] fusion(%q)": (
        _J + "layer_0_conv/short_conv/short_conv_gate/mul:",
        [(12.0, 0.5), (42.0, 0.5)]),
    "%_flash_forward.2 = (bf16[128,8192,64], f32[128,8192,1]) "
    "custom-call(%a, %b, %c), custom_call_target=\\\"tpu_custom_call\\\"": (
        _J + "layer_1_attn/gqa_attend/jit(_flash_forward)/pallas_call:",
        [(13.0, 4.0), (43.0, 4.0)]),
    "%gmm.49 = f32[32768,1536] custom-call(%c), "
    "custom_call_target=\\\"tpu_custom_call\\\"": (
        _J + "layer_1_moe/moe_experts/while/body/moe_grouped/"
        "jit(_moe_grouped_matmul)/jit(gmm)/pallas_call:",
        [(17.0, 2.0), (47.0, 2.0)]),
    "%fusion.7 = f32[32768,1536] fusion(%g)": (
        _J + "layer_1_moe/moe_experts/while/body/moe_grouped/mul:",
        [(19.0, 1.0), (49.0, 1.0)]),
    "%fusion.9 = f32[32768,2048] fusion(%d)": (
        _J + "layer_1_moe/moe_experts/while/body/scatter-add:",
        [(20.0, 3.0), (50.0, 3.0)]),
    "%sort.3 = s32[131072] sort(%e)": (
        _J + "layer_1_moe/moe_experts/sort:", [(23.0, 1.0), (53.0, 1.0)]),
    "%copy.3 = f32[8] copy(%e)": (None, [(25.0, 5.0), (55.0, 5.0)]),
}
MAIN_RUNS = [(10.0, 20.0), (40.0, 20.0)]


def write_profile(trace_dir, ops=None, runs=None):
    """``test_glm_dsa_cell.py``'s writer, with these operations and
    executions in its own's place."""
    glm_test = _load(os.path.join(ROOT, "tests", "benchmark_cells",
                                  "test_glm_dsa_cell.py"),
                     "glm_cell_test_for_lfm2_profile")
    glm_test.OPS = OPS if ops is None else ops
    glm_test.MAIN_RUNS = MAIN_RUNS if runs is None else runs
    return glm_test.write_profile(trace_dir)


def context(tmp_path, ops=None, runs=None):
    xplane_scopes.device_metadata.cache_clear()
    trace_dir = write_profile(tmp_path / ".bench_trace" / CELL, ops, runs)
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    reduced = trace_reduce.reduce_trace(trace_dir)
    return {"cell": cell, "trace": reduced, "peak": PEAK,
            "counters": {"rows_ok": 7, "seq": 8192, "bucket": 4,
                         "batch_rows": 3.5, "moe_tokens_held": 262144.0,
                         "moe_load_max_over_mean": 1.3,
                         "moe_passes": 4.0}}


def test_new_readers_by_hand(tmp_path):
    ctx = context(tmp_path)
    spec = ctx["cell"]["config_file"]["networkSpec"]
    t = ctx["trace"]
    # busy: 18.5 of each execution's 20 ms
    assert t["module_runs"] == 2 and t["busy_s"] == pytest.approx(0.037)
    need = fl.forward_flops_per_row(spec, 8192, 262144.0) * 7
    assert reader("lfm2_forward_mfu").read(ctx) == pytest.approx(
        100 * need / (0.037 * 197e12))
    # the grouped products: the custom calls under moe_experts (4 ms of
    # the scope's 14), 8 expert layers an execution, 2 executions
    pairs = 262144.0 / 8 * 3.5
    cost = fl.experts_cost(spec, pairs)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert reader("lfm2_experts_roofline").read(ctx) == pytest.approx(
        100 * least * 8 * 2 / 0.004)
    # two flash calls in two executions, two attention layers each: the
    # count is no whole number a layer, so the reader is silent ...
    assert reader("lfm2_flash_roofline").read(ctx) is None
    # ... and reads where each execution has one call an attention layer
    one = json.loads(json.dumps(ctx))
    one["cell"]["config_file"]["networkSpec"]["layer_types"] = KINDS[:5]
    cost = fl.flash_cost(spec, 4, 8192)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert reader("lfm2_flash_roofline").read(one) == pytest.approx(
        100 * 2 * least / 0.008)
    # under moe_experts 14 ms, 6 of them under moe_grouped
    assert reader("moe_dispatch_share").read(ctx) == pytest.approx(
        100 * 0.008 / 0.037)
    assert reader("short_conv_gate_share").read(ctx) == pytest.approx(
        100 * 0.001 / 0.037)
    assert reader("moe_load_max_over_mean").read(ctx) == 1.3
    # GLM's roofline reader still finds the calls under the nested scope
    assert xplane_scopes.seconds_under(
        t, xplane_scopes.for_run(ctx), "moe_experts") == (
        pytest.approx(0.014), 8)


def test_no_new_reader_reads_over_a_hundred(tmp_path):
    """A full bucket at the chip's peak reads 100 at most: the needed
    work of real rows over a trace in which every kernel runs at its
    roofline."""
    spec = body()["networkSpec"]
    per_bucket = 4 * fl.forward_flops_per_row(spec, 8192)
    step_ms = 1e3 * per_bucket / 197e12
    cost = fl.experts_cost(spec, 4 * 8192 * 4)
    gmm_ms = 1e3 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    flash = fl.flash_cost(spec, 4, 8192)
    flash_ms = 1e3 * max(flash["flops"] / 197e12, flash["bytes"] / 819e9)
    gmm, fla = list(OPS)[3], list(OPS)[2]
    ops = {gmm: (OPS[gmm][0], [(i * gmm_ms, gmm_ms) for i in range(8)]),
           fla: (OPS[fla][0], [(8 * gmm_ms + i * flash_ms, flash_ms)
                               for i in range(2)]),
           "%fusion.1 = f32[8] fusion(%p)": (
               OPS[list(OPS)[0]][0],
               [(8 * gmm_ms + 2 * flash_ms,
                 step_ms - 8 * gmm_ms - 2 * flash_ms)])}
    ctx = context(tmp_path, ops, [(0.0, step_ms)])
    ctx["counters"].update(rows_ok=4, batch_rows=4.0)
    assert ctx["trace"]["module_runs"] == 1
    assert ctx["trace"]["busy_s"] == pytest.approx(step_ms / 1e3, rel=1e-6)
    for name in ("lfm2_forward_mfu", "lfm2_experts_roofline",
                 "lfm2_flash_roofline"):
        assert reader(name).read(ctx) == pytest.approx(100.0, rel=1e-6), name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    """The parent's program, an untraced context, a profile without the
    scopes, the kernel or the counters: None, and nothing raised."""
    xplane_scopes.device_metadata.cache_clear()
    read = reader(name).read
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    assert read({"cell": cell, "trace": None, "peak": None,
                 "counters": {}}) is None
    # a profile of a program that has none of this: GPT-2's
    host_spans_test = _load(os.path.join(
        ROOT, "tests", "benchmark_cells", "test_host_spans.py"),
        "host_spans_test_for_lfm2")
    host_spans_test.write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    ctx = {"cell": cell, "trace": reduced, "peak": PEAK,
           "counters": {"seq": 8192, "bucket": 4, "rows_ok": 5,
                        "batch_rows": 3.0}}
    assert read(ctx) is None
    # GLM's program at the parent: moe_experts, but no moe_grouped
    # inside it and no counter of this family
    glm = _load(os.path.join(ROOT, "tests", "benchmark_cells",
                             "test_glm_dsa_cell.py"), "glm_profile_for_lfm2")
    xplane_scopes.device_metadata.cache_clear()
    shutil.rmtree(tmp_path / ".bench_trace")
    glm.write_profile(tmp_path / ".bench_trace" / CELL)
    ctx["trace"] = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    assert read(ctx) is None


# ----------------------------------------------------------- the pinned names

def test_the_program_names_every_scope_the_readers_read():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = {**body()["networkSpec"], **TINY}
    module = build_network({"dtype": "bfloat16", **spec})
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    text = jax.jit(lambda p, t: module.apply({"params": p}, t)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    # nested as the readers take them apart
    assert re.search(r"short_conv/short_conv_gate/", text)
    assert re.search(r"moe_experts/(while/body/)?(closed_call/)?"
                     r"moe_grouped/", text)
    driver = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                          "serve_hybrid_lm.py"))
    assert driver.ROW_STATS == tuple(module.row_stats) == (
        "moe_tokens_held", "moe_load_max_over_mean", "moe_passes")
    # GLM's program names the nested scope too, under its own
    glm = run.load_cell(ROOT, GLM_CELL)["config_file"]["networkSpec"]
    glm_tiny = _load(os.path.join(ROOT, "tests", "benchmark_cells",
                                  "test_glm_dsa_cell.py"), "glm_tiny").TINY
    module = build_network({"dtype": "bfloat16", **glm, **glm_tiny})
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    text = jax.jit(lambda p, t: module.apply({"params": p}, t)).lower(
        params, tokens).as_text(debug_info=True)
    assert re.search(r"moe_experts/(while/body/)?(closed_call/)?"
                     r"moe_grouped/", text)


def test_the_flash_reader_finds_the_grouped_query_call_by_its_name():
    """The custom call of a grouped-query forward lowers for the TPU
    under the name ``trace_reduce.FLASH_FORWARD`` matches, and reads K
    and V at their own 8 heads (no repeated copy)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import _flash_forward
    shape = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda q, k, v: _flash_forward(
        q, k, v, True, 0, 0, False)).trace(
        shape((1, 512, 32, 64), jnp.bfloat16),
        shape((1, 512, 8, 64), jnp.bfloat16),
        shape((1, 512, 8, 64), jnp.bfloat16)).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]+)"', text) == ["_fwd_kernel"]
    assert re.search(r"tensor<8x512x64xbf16>", text)
    assert re.search(trace_reduce.FLASH_FORWARD,
                     "%_flash_forward.2 = (bf16[128,8192,64]{2,1,0}, "
                     "f32[128,8192,1]{2,1,0}) custom-call(%bitcast.12)")


def test_reference_and_yardstick_import_nothing_of_the_program():
    for name in ("reference_lfm2.py", "flops_lfm2.py"):
        text = open(os.path.join(BENCH_DIR, name)).read()
        assert not re.search(r"^\s*(from|import) mmlspark_tpu", text,
                             re.M), name
    text = open(os.path.join(BENCH_DIR, "reference_lfm2.py")).read()
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "ragged_dot" not in text


# ------------------------------- near ties: the program's choices are handed on

def forced_by_hand(rng):
    """What ``reference_lfm2.forward(forced_tail=)`` hands back, by
    hand: eight expert layers whose cones are KINDS', two attention
    layers; every choice the reference's own."""
    import numpy as np
    cones = dict(zip(range(1, 9), (13, 11, 9, 7, 7, 5, 3, 1)))
    return {"logits": rng.normal(size=(4, 50)).astype(np.float32),
            "routed": {i: np.zeros((4, 32, 4), np.int64) for i in cones},
            "route_gap": {i: np.zeros((4, c)) for i, c in cones.items()},
            "route_miss": {i: np.zeros((4, c), np.int64)
                           for i, c in cones.items()},
            "operators": {i: rng.normal(size=(4, 32, 8)) for i in (1, 5)}}


LIMITS = {"class_gap": 0.05, "logit_rel_l2": 0.03, "route_gap": 0.005,
          "route_miss": 0.02, "attn_rel_l2": 0.02, "attn_late_rel_l2": 0.08,
          "served_not_model": 0, "unanswered": 0}


def over(checks):
    return [c["name"] for c in checks if c["value"] > c["limit"]]


def test_compare_holds_the_choices_and_the_logits_apart():
    """By hand, no model: the reference that took the program's choices
    gives the logits to compare with, ``route_gap`` and ``route_miss``
    hold the choices; a choice by another rule passes the first and not
    the others."""
    import numpy as np
    driver = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                          "serve_hybrid_lm.py"))
    rng = np.random.default_rng(0)
    forced, tr = forced_by_hand(rng), {"limits": LIMITS}
    model = forced["logits"] + 1e-3
    served = model.argmax(-1)
    forced["route_gap"][3][2, 5] = 4e-4   # a near tie that went the other
    forced["route_miss"][3][2, 5] = 1     # way: one expert of 4 x 224
    assert driver.attention_layers({"layer_types": KINDS}) == [1, 5]
    attn = driver.attended_by(forced, 4)
    assert attn.shape == (4, 2, 4, 8)
    assert (attn[:, 1] == forced["operators"][5][:, -4:]).all()
    checks = driver.compare(served, model, attn / 1.001, forced, tr, 0)
    assert [c["name"] for c in checks] == [
        "class_gap", "logit_rel_l2", "served_not_model", "unanswered",
        "route_gap", "route_miss", "attn_rel_l2", "attn_late_rel_l2"]
    value = {c["name"]: c["value"] for c in checks}
    assert value["attn_rel_l2"] == pytest.approx(1e-3 / 1.001) \
        == value["attn_late_rel_l2"]
    assert value["route_gap"] == 4e-4 and value["logit_rel_l2"] < 2e-3
    assert value["route_miss"] == pytest.approx(1 / (4 * 4 * 56))
    assert run.judge(checks)
    forced["route_gap"][7][0, 2] = 0.03   # a choice no rounding explains
    assert over(driver.compare(served, model, attn, forced, tr, 0)) == [
        "route_gap"]
    forced["route_gap"][7][0, 2] = 0.0


@pytest.mark.parametrize("fault, held_by", [
    ("small_margin_always", ["route_miss"]),
    ("first_attention_layer", ["attn_rel_l2"]),
    ("second_attention_layer", ["attn_late_rel_l2"]),
    ("wrong_token", ["class_gap", "served_not_model"])])
def test_compare_holds_a_fault_by_the_number_that_is_its_own(fault, held_by):
    import numpy as np
    driver = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                          "serve_hybrid_lm.py"))
    forced, tr = forced_by_hand(np.random.default_rng(1)), {"limits": LIMITS}
    model = forced["logits"] + 1e-3
    served = model.argmax(-1)
    attn = driver.attended_by(forced, 4)
    if fault == "small_margin_always":
        # a rule that errs by little at every position: each gap is a
        # near tie's, and one expert in four is not the reference's
        for i in forced["route_gap"]:
            forced["route_gap"][i][:] = 2e-3
            forced["route_miss"][i][:] = 1
    elif fault.endswith("attention_layer"):
        attn = attn.copy()          # the other layer's is as it should be
        attn[:, int(fault.startswith("second"))] *= 1.1
    else:
        served = served.copy()
        served[2] = forced["logits"][2].argmin()    # a reply gone astray
    checks = driver.compare(served, model, attn, forced, tr, 0)
    assert over(checks) == held_by
    value = {c["name"]: c["value"] for c in checks}
    if fault == "small_margin_always":
        assert value["route_miss"] == 0.25 and value["route_gap"] == 2e-3
    if fault.endswith("attention_layer"):
        assert value[held_by[0]] == pytest.approx(0.1)


def test_the_reference_takes_a_program_s_choices_at_the_tail():
    import numpy as np
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from hybrid_moe_tiny import ROWS, TINY as MODEL, build, reference
    _, params = build()
    driver = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                          "serve_hybrid_lm.py"))
    own = reference.forward(params, ROWS, MODEL)
    assert own["route_gap"][1].shape == (3, 0)          # nothing forced
    assert own["route_miss"][1].shape == (3, 0)
    # the positions that reach the last one by the convolutions alone
    cones = [reference.cone(MODEL, i) for i in range(5)]
    assert cones == [7, 7, 5, 3, 1]
    assert [reference.cone({"layer_types": KINDS, "conv_L_cache": 3}, i)
            for i in range(1, 9)] == [13, 11, 9, 7, 7, 5, 3, 1]
    # its own choices handed back: the same logits, no gap, none missed
    tail = driver.tail_of(own, 16)
    assert tail.shape == (3, 4, 16, 4)
    assert (tail[:, 1] == own["routed"][2][:, -16:]).all()
    same = reference.forward(params, ROWS, MODEL, forced_tail=tail)
    np.testing.assert_allclose(same["logits"], own["logits"], rtol=1e-6)
    for i in (1, 2, 3, 4):      # every forced position is looked at
        assert same["route_gap"][i].shape == (3, cones[i]) \
            == same["route_miss"][i].shape
        assert not same["route_gap"][i].any()
        assert not same["route_miss"][i].any()
    assert (same["routed"][4] == own["routed"][4]).all()
    # the order within a position's four is no matter
    flipped = reference.forward(params, ROWS, MODEL,
                                forced_tail=tail[..., ::-1])
    np.testing.assert_allclose(flipped["logits"], own["logits"],
                               rtol=1e-5, atol=1e-4)
    assert not any(m.any() for m in flipped["route_miss"].values())
    # choices made without the bias: taken over the cone and nowhere
    # else, and seen to be far off there
    other = reference.forward(params, ROWS, MODEL, bias_in_choice=False)
    forced = reference.forward(params, ROWS, MODEL,
                               forced_tail=driver.tail_of(other, 16))
    for i in (1, 2, 3, 4):
        c = cones[i]
        assert (forced["routed"][i][:, -c:] == other["routed"][i][:, -c:]
                ).all()
        # before the widest cone nothing was forced in any layer
        assert (forced["routed"][i][:, :-7] == own["routed"][i][:, :-7]
                ).all()
    assert max(g.max() for g in forced["route_gap"].values()) > 0.01
    missed = np.concatenate([m.ravel()
                             for m in forced["route_miss"].values()])
    assert missed.min() >= 0 and missed.max() <= 4
    assert missed.sum() / (4 * missed.size) > 0.02
    # a miss is counted where the choice lies under the reference's own
    gaps = np.concatenate([g.ravel()
                           for g in forced["route_gap"].values()])
    assert ((missed > 0) == (gaps > 0)).all()


# ------------------------------------------------- the cell, at a tiny size

def make_root(tmp_path, limits=None):
    """A checkout of the benchmark alone with this cell cut to a toy."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(root, "benchmark", "configs", CONFIG + ".json")
    cfg = json.load(open(path))
    cfg["networkSpec"].update(TINY)
    json.dump(cfg, open(path, "w"))
    path = os.path.join(root, "benchmark", "traffic", MIX + ".json")
    mix = json.load(open(path))
    mix["arrivals"]["rate_per_s"] = 20.0
    mix.update(client_threads=16, reply_timeout_s=60)
    # bfloat16 against float32 at 64 wide: a score's rounding is ten
    # times the cell's, so the toy gets room the cell has not
    mix["limits"].update(limits or {"class_gap": 0.3, "logit_rel_l2": 0.1,
                                    "route_gap": 0.05, "route_miss": 0.03,
                                    "attn_rel_l2": 0.1,
                                    "attn_late_rel_l2": 0.1})
    json.dump(mix, open(path, "w"))
    json.dump(BENCH, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def line(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("lfm2"))
    return run.run_cell(root, CELL, 2 ** 31 + 7, 1.5, False,
                        require_tpu=False)


def test_cell_end_to_end(line):
    assert line["correct"] is True
    assert line["attempted"] == 30 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] <= \
        line["metrics"]["serve_p95_ms"]["value"]
    assert set(line["compared"]) == {"class_gap", "logit_rel_l2",
                                     "route_gap", "route_miss",
                                     "attn_rel_l2", "attn_late_rel_l2",
                                     "served_not_model", "unanswered"}
    assert line["info"]["attn_rel_l2_by_layer"] == [
        line["compared"][n]["value"]
        for n in ("attn_rel_l2", "attn_late_rel_l2")]
    assert 0 < line["compared"]["attn_rel_l2"]["value"] < 0.05
    assert len(line["info"]["rows_rel_l2"]) == 8 == len(
        line["info"]["rows_route_gap"])
    # with the program's choices handed on, every row reads a rounding
    assert max(line["info"]["rows_rel_l2"]) < 0.05
    assert line["compared"]["route_gap"]["value"] == max(
        line["info"]["rows_route_gap"])
    assert 0 <= line["compared"]["route_miss"]["value"] < 0.03
    assert line["compared"]["served_not_model"]["value"] == 0
    info = line["info"]
    assert info["recompiles"] == 0 and info["sampled"] == 8
    # the model's counters of the window: a row a request, every routed
    # pair held (32 tokens x 4 experts x 8 expert layers a row)
    assert info["rows_scored"] == 30
    assert info["moe_tokens_held"] == 32 * 4 * 8
    assert info["moe_passes"] == 1.0 and info["weights_cast_leaves"] == 0
    assert info["moe_load_max_over_mean"] >= 1.0
