"""The ``mellum2_score_16k_steady`` cell's own tests: CPU only, a tiny
preset. The configuration's entry and file (with its cuts, the widths
read by this configuration's own keys), the benchmark as it stands with
five cells, the yardstick ``flops_mellum2`` against hand counts, each new
reader by hand on a profile written by hand and silent with nothing to
read, the names the readers find things by, the comparison by hand, the
driver end to end and the controls of ``correct``.

Four of the checks here are those of benchmark tests that assert the
benchmark of PR 34 (four cells) or uncut configurations and are marked
expected failures from tests/conftest.py: see PERF.md, Open questions
0i. Each repeat holds every assertion of the test it stands for and
changes one thing, marked. No topology or TPU call is made anywhere in
this file.
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
HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mellum2_score_16k_steady"
LFM2_CELL = "lfm2_score_8k_steady"
GLM_CELL = "glm52_score_8k_steady"
CONFIG = "mellum2-12b-a2.5b-stage"
MIX = "poisson_steady_16k_mellum2"
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_mellum2")
import flops_mellum2 as fl   # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce          # noqa: E402
import xplane_scopes         # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_METRICS = ["mellum2_forward_mfu", "mellum2_experts_roofline",
               "mellum2_flash_roofline", "swa_flash_roofline",
               "swa_attend_share"]
LFM2_METRICS = ["lfm2_forward_mfu", "lfm2_experts_roofline",
                "lfm2_flash_roofline", "moe_dispatch_share",
                "short_conv_gate_share"]
GENERIC = ["serve_queue_wait_ms", "scorer_device_wait_ms",
           "device_idle_serve", "serve_token_wait_ms",
           "serve_dispatch_wait_ms", "serve_worker_host_ms",
           "device_idle_serve_named", "moe_load_max_over_mean"]
SCOPES = ("swa_attend", "gqa_attend", "gqa_project", "moe_route",
          "moe_experts", "moe_grouped", "moe_combine", "lm_head_last")
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {"vocab_size": 128, "max_len": 48, "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 8, "moe_intermediate_size": 32,
        "num_experts": 16}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def body():
    return json.load(open(os.path.join(BENCH_DIR, "configs",
                                       CONFIG + ".json")))


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


def driver():
    return run.load_module(os.path.join(BENCH_DIR, "drivers",
                                        "serve_mellum2.py"))


# ------------------------------------------------- BENCHMARK.json and the file

def test_config_entry_and_its_file_with_cuts():
    """``test_config_entry_and_its_file`` with ``reduced`` as it stands
    (the one change) and the widths read by this configuration's own
    keys."""
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG for w in BENCH["workloads"])
    b = body()
    assert b["source"] == config["source"] == SOURCE
    assert b["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    # the published values of what was cut stand beside the cut ones
    pub = b["published"]
    assert pub["num_hidden_layers"] == 28 == len(pub["layer_types"])
    assert pub["layer_types"] == KINDS * 7
    assert pub["mlp_layer_types"] == ["sparse"] * 28
    # published layers 0-7: two whole periods, every layer sparse
    assert b["num_hidden_layers"] == 8
    assert b["layer_types"] == pub["layer_types"][:8] == KINDS * 2
    assert b["mlp_layer_types"] == ["sparse"] * 8
    spec = b["networkSpec"]
    assert spec["type"] == "hybrid_moe_lm"
    assert spec["layer_types"] == b["layer_types"]
    assert spec["num_dense_layers"] == 0
    assert len(spec["layer_types"]) == b["num_hidden_layers"]
    # no width is cut: the file's published keys and what is run agree
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "vocab_size", "sliding_window",
                "rope_parameters", "tie_word_embeddings"):
        assert spec[key] == b[key], key
    assert spec["norm_eps"] == b["rms_norm_eps"] == 1e-6
    assert (b["hidden_size"], b["head_dim"], b["moe_intermediate_size"],
            b["num_attention_heads"], b["num_key_value_heads"],
            b["num_experts"], b["num_experts_per_tok"], b["vocab_size"],
            b["sliding_window"], b["intermediate_size"]) == (
        2304, 128, 896, 32, 4, 64, 8, 98304, 1024, 7168)
    full = b["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"]) == (
        "yarn", 500000, 16, 8192, 32, 1, 1.2772588722239782)
    assert b["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    assert b["norm_topk_prob"] is True and b["model_type"] == "mellum"
    assert b["tie_word_embeddings"] is False
    assert b["attention_bias"] is False
    assert b["max_position_embeddings"] == 131072
    # softmax scores, no bias, nothing added to the chosen scores' sum
    assert (spec["scoring_func"], spec["use_expert_bias"],
            spec["gate_norm_eps"], spec["routed_scaling_factor"]) == (
        "softmax", False, 0.0, 1.0)
    assert spec["max_len"] == 16384
    assert b["deployment"]["pipeline_stages"] == 4
    assert b["deployment"]["layers_a_stage"] == [8, 8, 8, 4]
    for key in ("qk_norm", "rope", "yarn_truncate", "window", "router",
                "max_len", "initial_weights"):
        assert b["assumed"][key], key
    assert any("no decode" in d for d in b["departures"])
    assert any("head" in d and "first stage" in d for d in b["departures"])
    assert any("MTP" in d for d in b["departures"])
    assert b["parameters"] == fl.parameters(spec) == 3_794_968_832
    assert b["parameter_bytes"] == 2 * b["parameters"]
    assert "bfloat16" in b["precision"]


def test_the_cell_and_what_it_reports():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "chips": 1, "traffic": MIX}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + ["moe_dispatch_share"] + NEW_METRICS)
    for m in BENCH["per_layer"]:
        # the other families' step readers stay theirs
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline",
                         "lfm2_forward_mfu", "lfm2_experts_roofline",
                         "lfm2_flash_roofline", "short_conv_gate_share"):
            assert CELL not in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC + ["moe_dispatch_share"]:
            assert m["workloads"][-1] == CELL
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in NEW_METRICS] == [
        ("model step", "higher"), ("kernels", "higher"),
        ("kernels", "higher"), ("kernels", "higher"),
        ("model step", "lower")]
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_mellum2" and mix["batch_size"] == 2
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["trace_window_s"],
            mix["reply_timeout_s"]) == (5.0, 1, 4, 8, 12, 120)
    assert isinstance(mix["arrivals"]["gap_seed"], int)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2", "route_gap",
                                  "route_miss", "swa_rel_l2",
                                  "full_rel_l2", "served_not_model",
                                  "unanswered"}
    assert set(mix["limits_why"]) == set(mix["limits"])
    assert all(len(why) > 20 for why in mix["limits_why"].values())
    # every sampled row decides: none is set aside, so no margin
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.2
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    # layer 0 is the window's arithmetic alone; layer 3 lies after three
    # expert layers
    assert 0 < mix["limits"]["swa_rel_l2"] \
        <= mix["limits"]["full_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


def test_the_lfm2_cell_reports_what_it_did():
    """``test_lfm2_cell.py::test_the_cell_and_what_it_reports`` as the
    benchmark stands, every assertion of it, changed in one place: the
    generic readers' ``workloads`` (and ``moe_dispatch_share``'s, which
    ISSUE 36 appends this cell to) list the LFM2 cell and then this one,
    where the original asserts the LFM2 cell last (or alone)."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == LFM2_CELL)
    assert cell == {**cell, "config": "lfm2-24b-a2b-stage", "chips": 1,
                    "traffic": "poisson_steady_8k_lfm2"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, LFM2_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + LFM2_METRICS)
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline"):
            assert LFM2_CELL not in m["workloads"]
        if m["name"] in LFM2_METRICS:
            assert m["workloads"] == (
                [LFM2_CELL, CELL] if m["name"] == "moe_dispatch_share"
                else [LFM2_CELL])              # the one change ...
            assert m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC:
            assert m["workloads"][-2:] == [LFM2_CELL, CELL]   # ... and here
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in LFM2_METRICS] == [
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
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.1
    assert mix["limits"]["class_gap"] == 0.05
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    assert 0 < mix["limits"]["attn_rel_l2"] \
        < mix["limits"]["attn_late_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


GLM_METRICS = ["glm_forward_mfu", "dsa_attend_roofline", "dsa_select_share",
               "moe_experts_roofline", "moe_load_max_over_mean"]


def test_the_glm_cell_reports_what_it_did():
    """``test_lfm2_cell.py::test_the_glm_cell_reports_what_it_did`` as
    the benchmark stands, every assertion of it, changed in one place:
    ``moe_load_max_over_mean`` lists the GLM cell, the LFM2 cell and
    then this one (ISSUE 36 appends it)."""
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
                [GLM_CELL, LFM2_CELL, CELL]
                if m["name"] == "moe_load_max_over_mean"
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
    assert 0 <= mix["limits"]["near_tie_rows"] <= mix["sample_requests"] - 2
    assert 0 < mix["near_tie_margin"] < 0.01
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]


def test_benchmark_json_is_still_well_formed():
    """``test_lfm2_cell.py::test_benchmark_json_is_still_well_formed`` as
    the benchmark stands, every assertion of it, changed in one place:
    the lists of cells, of configurations and of per-layer entries have
    this PR's at their end."""
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", GLM_CELL,
                     LFM2_CELL, CELL]          # the one change
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)
    # ... and what PR 36 may not have moved
    assert [c["name"] for c in BENCH["configs"]] == [
        "gpt2-medium", "gpt2-xl", "glm-5.2-ep16", "lfm2-24b-a2b-stage",
        CONFIG]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 40
    # the new entries are the last of their lists, PR 34's before them
    assert [m["name"] for m in BENCH["per_layer"]][-10:] == \
        LFM2_METRICS + NEW_METRICS
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"train_tokens_per_s": 0.01, "serve_p50_ms": 0.03,
                      "serve_p95_ms": 0.07, "setup_s": 0.1}


# ------------------------------------------------------- the yardstick by hand

def test_flops_by_hand_for_one_tiny_shape():
    s = {**TINY, "num_experts_per_tok": 8,
         "layer_types": ["sliding_attention", "full_attention"]}
    length = 48
    assert fl.causal_pairs(length) == 1176
    # query p sees min(p + 1, 8) keys
    assert fl.banded_pairs(length, 8) == sum(
        min(p + 1, 8) for p in range(48)) == 36 + 40 * 8
    assert fl.banded_pairs(5, 8) == fl.causal_pairs(5) == 15
    assert fl.head_dim(s) == 16
    attn = 2 * 64 * 8 * 16 + 2 * 64 * 2 * 16 + 2 * 16
    assert fl.attention_params(s) == attn
    expert = 3 * 64 * 32
    assert fl.expert_params(s) == expert
    assert fl.layer_params(s) == attn + 2 * 64 + 16 * (64 + expert)
    assert fl.parameters(s) == 2 * 128 * 64 + 64 + 2 * fl.layer_params(s)
    banded = 2 * 2 * 8 * 356 * 16
    causal = 2 * 2 * 8 * 1176 * 16
    moved = length * 16 * (2 * 8 + 2 * 2) * 2 + 4 * 8 * length
    assert fl.flash_cost(s, "sliding_attention", 1, length) == {
        "flops": banded, "bytes": moved}
    assert fl.flash_cost(s, "full_attention", 1, length) == {
        "flops": causal, "bytes": moved}
    # a bucket of 2: twice the pairs, K and V once a key/value head
    assert fl.flash_cost(s, "sliding_attention", 2, length)["flops"] \
        == 2 * banded
    proj = 2 * length * (attn - 32)
    router = 2 * length * 64 * 16
    pairs = 2 * length * 8                        # two layers
    assert fl.expected_pairs(s, length) == pairs
    routed = 2 * 3 * 64 * 32 * pairs
    head = 2 * 64 * 128
    want = 2 * proj + banded + causal + 2 * router + routed + head
    assert fl.forward_flops_per_row(s, length) == want
    # the program's own count of the pairs takes the expectation's place
    assert fl.forward_flops_per_row(s, length, 200.0) == \
        want - routed + 2 * 3 * 64 * 32 * 200.0
    assert fl.experts_cost(s, 40.0) == {
        "flops": 2 * 3 * 64 * 32 * 40.0,
        "bytes": (16 * expert + 40.0 * 2 * 64) * 2}


def test_flops_at_the_cell_s_size():
    """ISSUE 36's arithmetic, checked against the tree."""
    spec = body()["networkSpec"]
    assert fl.attention_params(spec) == 21_233_664 + 256
    assert fl.expert_params(spec) == 6_193_152
    assert 64 * fl.expert_params(spec) == 396_361_728
    assert 64 * 2304 == 147_456                                # a router
    assert fl.layer_params(spec) == 417_747_712
    assert 98304 * 2304 == 226_492_416
    assert fl.parameters(spec) == 3_794_968_832                # 7.59 GB
    assert fl.banded_pairs(16384, 1024) == 16_253_440
    assert fl.causal_pairs(16384) == 134_225_920
    assert fl.banded_pairs(16384, 1024) / fl.causal_pairs(16384) \
        == pytest.approx(0.121, abs=0.001)
    per_row = fl.forward_flops_per_row(spec, 16384)
    assert per_row == pytest.approx(24.59e12, rel=1e-3)
    experts = fl.gated_mlp_flops(2304, 896, fl.expected_pairs(spec, 16384))
    assert fl.expected_pairs(spec, 16384) == 1_048_576
    assert experts / per_row == pytest.approx(0.53, abs=0.005)
    proj = 8 * 2 * 16384 * (fl.attention_params(spec) - 256)
    assert proj / per_row == pytest.approx(0.23, abs=0.005)
    full = 2 * fl.flash_cost(spec, "full_attention", 1, 16384)["flops"]
    banded = 6 * fl.flash_cost(spec, "sliding_attention", 1, 16384)["flops"]
    assert full / per_row == pytest.approx(0.18, abs=0.005)
    assert banded / per_row == pytest.approx(0.065, abs=0.001)
    # without the window the same row needs 36.19 TFLOP: it removes 32%
    every = fl.forward_flops_per_row(
        {**spec, "layer_types": ["full_attention"] * 8}, 16384)
    assert every == pytest.approx(36.19e12, rel=1e-3)
    assert 1 - per_row / every == pytest.approx(0.32, abs=0.005)
    short = fl.forward_flops_per_row({**spec, "max_len": 8192}, 8192)
    assert 1 - short / fl.forward_flops_per_row(
        {**spec, "layer_types": ["full_attention"] * 8}, 8192) \
        == pytest.approx(0.18, abs=0.01)
    # the fallback of one period, were it ever needed
    one = {**spec, "layer_types": KINDS}
    assert fl.parameters(one) == 2_123_977_984


# --------------------------------------------- the readers on a written profile

_J = "jit(tpu_model_forward)/HybridMoELM/"
_FLASH = ("%_flash_forward.{n} = (bf16[64,16384,128], f32[64,16384,1]) "
          "custom-call(%a, %b, %c), custom_call_target=\\\"tpu_custom_call\\\"")
SWA, GQA, GMM = _FLASH.format(n=3), _FLASH.format(n=11), (
    "%gmm.49 = f32[32768,896] custom-call(%c), "
    "custom_call_target=\\\"tpu_custom_call\\\"")


def at(first, step, n, length, runs=(0.0, 30.0)):
    """n intervals of ``length`` ms from ``first`` every ``step``, in
    each execution."""
    return [(r + first + i * step, length) for r in runs for i in range(n)]


OPS = {  # name -> (scope or None, [(start ms, length ms)])
    SWA: (_J + "layer_0_attn/swa_attend/jit(_flash_forward)/pallas_call:",
          at(10.0, 0.6, 6, 0.5)),
    "%copy.9 = bf16[2,32,16384,128] copy(%q)": (
        _J + "layer_0_attn/swa_attend/transpose:", at(13.6, 1.0, 1, 0.4)),
    GQA: (_J + "layer_3_attn/gqa_attend/jit(_flash_forward)/pallas_call:",
          at(14.0, 1.6, 2, 1.5)),
    GMM: (_J + "layer_1_moe/moe_experts/while/body/moe_grouped/"
          "jit(_moe_grouped_matmul)/jit(gmm)/pallas_call:",
          at(17.2, 1.0, 1, 1.0)),
    "%fusion.7 = bf16[32768,896] fusion(%g)": (
        _J + "layer_1_moe/moe_experts/while/body/moe_grouped/mul:",
        at(18.2, 1.0, 1, 0.5)),
    "%fusion.9 = bf16[32768,2304] fusion(%d)": (
        _J + "layer_1_moe/moe_experts/while/body/gather:",
        at(18.7, 1.0, 1, 1.0)),
    "%fusion.12 = f32[32768,2304] fusion(%e)": (
        _J + "layer_1_moe/moe_experts/moe_combine/gather:",
        at(19.7, 1.0, 1, 0.8)),
    "%copy.3 = f32[8] copy(%e)": (None, at(20.5, 1.0, 1, 4.0)),
}
MAIN_RUNS = [(10.0, 20.0), (40.0, 20.0)]
BUSY_MS = 2 * (6 * 0.5 + 0.4 + 2 * 1.5 + 1.0 + 0.5 + 1.0 + 0.8 + 4.0)


def write_profile(trace_dir, ops=None, runs=None):
    """``test_glm_dsa_cell.py``'s writer, with these operations and
    executions in its own's place."""
    glm_test = _load(os.path.join(HERE, "test_glm_dsa_cell.py"),
                     "glm_cell_test_for_mellum2_profile")
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
            "counters": {"rows_ok": 7, "seq": 16384, "bucket": 2,
                         "batch_rows": 1.5, "moe_tokens_held": 1048576.0,
                         "moe_load_max_over_mean": 1.5,
                         "moe_passes": 8.0}}


def least(cost):
    return max(cost["flops"] / 197e12, cost["bytes"] / 819e9)


def test_new_readers_by_hand(tmp_path):
    ctx = context(tmp_path)
    spec = ctx["cell"]["config_file"]["networkSpec"]
    t = ctx["trace"]
    busy = BUSY_MS / 1e3
    assert t["module_runs"] == 2 and t["busy_s"] == pytest.approx(busy)
    need = fl.forward_flops_per_row(spec, 16384, 1048576.0) * 7
    assert reader("mellum2_forward_mfu").read(ctx) == pytest.approx(
        100 * need / (busy * 197e12))
    # the grouped products: the custom calls under moe_experts (1 ms an
    # execution), 8 layers an execution, 2 executions
    pairs = 1048576.0 / 8 * 1.5
    assert reader("mellum2_experts_roofline").read(ctx) == pytest.approx(
        100 * least(fl.experts_cost(spec, pairs)) * 8 * 2 / 0.002)
    # one call a sliding layer (6) and a full layer (2) an execution,
    # told apart by the scope around them
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100 * 12 * least(fl.flash_cost(
            spec, "sliding_attention", 2, 16384)) / 0.006)
    assert reader("mellum2_flash_roofline").read(ctx) == pytest.approx(
        100 * 4 * least(fl.flash_cost(
            spec, "full_attention", 2, 16384)) / 0.006)
    # under swa_attend: the six calls and the copy beside them
    assert reader("swa_attend_share").read(ctx) == pytest.approx(
        100 * 0.0068 / busy)
    # under moe_experts 3.3 ms an execution, 1.5 of them under moe_grouped
    assert reader("moe_dispatch_share").read(ctx) == pytest.approx(
        100 * 0.0036 / busy)
    assert reader("moe_load_max_over_mean").read(ctx) == 1.5
    # a call short (5 of 6 sliding layers' in one execution): no whole
    # number a layer, so the reader is silent; the full layers' still reads
    ops = {**OPS, SWA: (OPS[SWA][0], OPS[SWA][1][:-1])}
    short = context(tmp_path / "short", ops)
    assert reader("swa_flash_roofline").read(short) is None
    assert reader("mellum2_flash_roofline").read(short) is not None


def test_no_new_reader_reads_over_a_hundred(tmp_path):
    """A full bucket at the chip's peak reads 100 at most: the needed
    work of real rows over a trace in which every kernel runs at its
    roofline."""
    spec = body()["networkSpec"]
    step_ms = 1e3 * 2 * fl.forward_flops_per_row(spec, 16384) / 197e12
    gmm_ms = 1e3 * least(fl.experts_cost(spec, 2 * 16384 * 8))
    swa_ms = 1e3 * least(fl.flash_cost(spec, "sliding_attention", 2, 16384))
    full_ms = 1e3 * least(fl.flash_cost(spec, "full_attention", 2, 16384))
    used = 8 * gmm_ms + 6 * swa_ms + 2 * full_ms
    ops = {GMM: (OPS[GMM][0], at(0.0, gmm_ms, 8, gmm_ms, (0.0,))),
           SWA: (OPS[SWA][0], at(8 * gmm_ms, swa_ms, 6, swa_ms, (0.0,))),
           GQA: (OPS[GQA][0], at(8 * gmm_ms + 6 * swa_ms, full_ms, 2,
                                 full_ms, (0.0,))),
           "%fusion.1 = f32[8] fusion(%p)": (
               _J + "layer_0_attn/gqa_project/dot_general:",
               [(used, step_ms - used)])}
    ctx = context(tmp_path, ops, [(0.0, step_ms)])
    ctx["counters"].update(rows_ok=2, batch_rows=2.0)
    assert ctx["trace"]["module_runs"] == 1
    assert ctx["trace"]["busy_s"] == pytest.approx(step_ms / 1e3, rel=1e-6)
    for name in NEW_METRICS[:4]:
        assert reader(name).read(ctx) == pytest.approx(100.0, rel=1e-6), name
    assert reader("swa_attend_share").read(ctx) == pytest.approx(
        100 * 6 * swa_ms / step_ms)
    assert reader("swa_attend_share").read(ctx) < 100


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    """An untraced context, a profile without the scopes, the kernel or
    the counters (GPT-2's, GLM's, LFM2's programs): None, and nothing
    raised."""
    xplane_scopes.device_metadata.cache_clear()
    read = reader(name).read
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    assert read({"cell": cell, "trace": None, "peak": None,
                 "counters": {}}) is None
    host_spans_test = _load(os.path.join(HERE, "test_host_spans.py"),
                            "host_spans_test_for_mellum2")
    host_spans_test.write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    ctx = {"cell": cell, "trace": reduced, "peak": PEAK,
           "counters": {"seq": 16384, "bucket": 2, "rows_ok": 5,
                        "batch_rows": 1.5}}
    assert read(ctx) is None
    # LFM2's program: gqa_attend and moe_experts, but no swa_attend, two
    # flash calls where this configuration has eight, and no counter
    for other, tag in (("test_glm_dsa_cell.py", "glm"),
                       ("test_lfm2_cell.py", "lfm2")):
        module = _load(os.path.join(HERE, other), f"{tag}_profile_for_m2")
        xplane_scopes.device_metadata.cache_clear()
        shutil.rmtree(tmp_path / ".bench_trace")
        module.write_profile(tmp_path / ".bench_trace" / CELL)
        ctx["trace"] = trace_reduce.reduce_trace(
            str(tmp_path / ".bench_trace" / CELL))
        assert read(ctx) is None, other


# ----------------------------------------------------------- the pinned names

def test_the_program_names_every_scope_the_readers_read():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = {**body()["networkSpec"], **TINY}
    module = build_network({"dtype": "bfloat16", **spec})
    tokens = jnp.zeros((2, 48), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    text = jax.jit(lambda p, t: module.apply({"params": p}, t)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    # a sliding layer's attention lies under swa_attend, a full one's
    # under gqa_attend, and never the other way round
    assert re.search(r"layer_0_attn/swa_attend/", text)
    assert re.search(r"layer_3_attn/gqa_attend/", text)
    assert not re.search(r"layer_0_attn/gqa_attend/", text)
    assert not re.search(r"layer_3_attn/swa_attend/", text)
    assert re.search(r"moe_experts/(while/body/)?(closed_call/)?"
                     r"moe_grouped/", text)
    assert re.search(r"moe_experts/moe_combine/", text)
    assert driver().ROW_STATS == tuple(module.row_stats) == (
        "moe_tokens_held", "moe_load_max_over_mean", "moe_passes")
    assert driver().TAILS == tuple(module.row_outputs)
    # the two block counters, as the kernel's own plan counts them
    assert (module.flash_window_blocks, module.flash_causal_blocks) == (1, 1)
    full = build_network({"dtype": "bfloat16",
                          **body()["networkSpec"], "max_len": 4096})
    # off the chip a fetch block is 256 rows: 16 a side at 4096 tokens,
    # a window of 1024 is 4 blocks wide plus the diagonal's
    assert (full.flash_window_blocks, full.flash_causal_blocks) == (
        sum(min(i + 1, 5) for i in range(16)), 136)


def test_the_flash_readers_find_the_windowed_call_by_its_name():
    """The custom call of a windowed grouped-query forward lowers for the
    TPU under the name ``trace_reduce.FLASH_FORWARD`` matches, with the
    band as its key axis, and reads K and V at their own 4 heads."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import _flash_forward
    shape = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda q, k, v: _flash_forward(
        q, k, v, True, 0, 0, False, 256)).trace(
        shape((1, 2048, 32, 128), jnp.bfloat16),
        shape((1, 2048, 4, 128), jnp.bfloat16),
        shape((1, 2048, 4, 128), jnp.bfloat16)).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]+)"', text) == ["_fwd_kernel"]
    assert re.search(r"tensor<4x2048x128xbf16>", text)
    # 8 query blocks of 256 (off the chip), each with a band of 2
    from mmlspark_tpu.ops.flash_attention import tile_plan
    plan = tile_plan(2048, 2048, 128, True, window=256)
    assert plan.grid == (8, 8) and plan.band_blocks == 2
    assert plan.counts()["blocks_grid"] == 16
    assert plan.counts()["blocks_run"] == 15
    assert re.search(trace_reduce.FLASH_FORWARD, SWA.replace('\\"', '"'))


def test_reference_and_yardstick_import_nothing_of_the_program():
    for name in ("reference_mellum2.py", "flops_mellum2.py",
                 "trace_mellum2.py", "control_mellum2.py"):
        text = open(os.path.join(BENCH_DIR, name)).read()
        assert not re.search(r"^\s*(from|import) mmlspark_tpu", text,
                             re.M), name
    text = open(os.path.join(BENCH_DIR, "reference_mellum2.py")).read()
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "ragged_dot" not in text
    assert "float32" in text and "bfloat16" not in text.split('"""', 2)[2]


# ---------------------------------------------------- the comparison, by hand

def forced_by_hand(rng):
    """What ``reference_mellum2.forward(forced_tail=, keep_tail=4)``
    hands back, by hand: eight layers, the choice taken over at the last
    position of each, layers 0 and 3 kept; every choice the
    reference's own."""
    import numpy as np
    return {"logits": rng.normal(size=(4, 50)).astype(np.float32),
            "routed": {i: np.zeros((4, 48, 8), np.int64) for i in range(8)},
            "route_gap": {i: np.zeros((4, 1)) for i in range(8)},
            "route_miss": {i: np.zeros((4, 1), np.int64) for i in range(8)},
            "operators": {i: rng.normal(size=(4, 4, 8)) for i in (0, 3)}}


LIMITS = {"class_gap": 0.05, "logit_rel_l2": 0.03, "route_gap": 0.005,
          "route_miss": 0.02, "swa_rel_l2": 0.02, "full_rel_l2": 0.08,
          "served_not_model": 0, "unanswered": 0}
SPEC8 = {"layer_types": KINDS * 2, "num_experts_per_tok": 8}


def over(checks):
    return [c["name"] for c in checks if c["value"] > c["limit"]]


def attention_tail_of(forced):
    """``attention_tail`` (n, layers, 4, d) that agrees with the kept
    layers of ``forced`` and is noise elsewhere."""
    import numpy as np
    tail = np.random.default_rng(5).normal(size=(4, 8, 4, 8))
    for layer, a in forced["operators"].items():
        tail[:, layer] = a
    return tail


def test_compare_holds_the_choices_the_window_and_the_logits_apart():
    import numpy as np
    drv = driver()
    rng = np.random.default_rng(0)
    forced, tr = forced_by_hand(rng), {"limits": LIMITS}
    model = forced["logits"] + 1e-3
    served = model.argmax(-1)
    forced["route_gap"][3][2, 0] = 4e-4   # a near tie that went the other
    forced["route_miss"][3][2, 0] = 1     # way: one expert of 8 x 32
    assert drv.held_layers(SPEC8) == {"swa_rel_l2": 0, "full_rel_l2": 3}
    assert drv.last_choice(np.zeros((4, 8, 16, 8))).shape == (4, 8, 1, 8)
    attn = attention_tail_of(forced)
    checks = drv.compare(served, model, attn / 1.001, forced, SPEC8, tr, 0)
    assert [c["name"] for c in checks] == [
        "class_gap", "logit_rel_l2", "served_not_model", "unanswered",
        "route_gap", "route_miss", "swa_rel_l2", "full_rel_l2"]
    value = {c["name"]: c["value"] for c in checks}
    assert value["swa_rel_l2"] == pytest.approx(1e-3 / 1.001) \
        == value["full_rel_l2"]
    assert value["route_gap"] == 4e-4 and value["logit_rel_l2"] < 2e-3
    assert value["route_miss"] == pytest.approx(1 / (8 * 4 * 8))
    assert run.judge(checks)
    forced["route_gap"][7][0, 0] = 0.03   # a choice no rounding explains
    assert over(drv.compare(served, model, attn, forced, SPEC8, tr, 0)) \
        == ["route_gap"]


@pytest.mark.parametrize("fault, held_by", [
    ("small_margin_always", ["route_miss"]),
    ("first_sliding_layer", ["swa_rel_l2"]),
    ("first_full_layer", ["full_rel_l2"]),
    ("another_layer", []),
    ("wrong_token", ["class_gap", "served_not_model"])])
def test_compare_holds_a_fault_by_the_number_that_is_its_own(fault, held_by):
    import numpy as np
    drv = driver()
    forced, tr = forced_by_hand(np.random.default_rng(1)), {"limits": LIMITS}
    model = forced["logits"] + 1e-3
    served = model.argmax(-1)
    attn = attention_tail_of(forced)
    if fault == "small_margin_always":
        for i in forced["route_gap"]:
            forced["route_gap"][i][:] = 2e-3
            forced["route_miss"][i][:] = 2
    elif fault.endswith("layer"):
        layer = {"first_sliding_layer": 0, "first_full_layer": 3,
                 "another_layer": 5}[fault]
        attn[:, layer] *= 1.1       # layer 5 is compared with nothing
    else:
        served = served.copy()
        # a reply gone astray
        served[2] = forced["logits"][2].argmin()
    checks = drv.compare(served, model, attn, forced, SPEC8, tr, 0)
    assert over(checks) == held_by
    value = {c["name"]: c["value"] for c in checks}
    if fault == "small_margin_always":
        assert value["route_miss"] == 0.25 and value["route_gap"] == 2e-3
    if held_by and fault.endswith("layer"):
        assert value[held_by[0]] == pytest.approx(0.1)


def test_the_reference_takes_a_program_s_choices_at_the_last_position():
    import numpy as np
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from mellum2_tiny import ROWS, TINY as MODEL, build, reference
    _, params = build()
    drv = driver()
    own = reference.forward(params, ROWS, MODEL)
    assert own["route_gap"][1].shape == (3, 0)          # nothing forced
    assert own["route_miss"][1].shape == (3, 0)
    # its own choices handed back: the same logits, no gap, none missed
    tail = drv.tail_of(own, 16)
    assert tail.shape == (3, 3, 16, 8)
    same = reference.forward(params, ROWS, MODEL,
                             forced_tail=drv.last_choice(tail))
    np.testing.assert_allclose(same["logits"], own["logits"], rtol=1e-6)
    for i in range(3):          # every forced position is looked at
        assert same["route_gap"][i].shape == (3, 1) \
            == same["route_miss"][i].shape
        assert not same["route_gap"][i].any()
        assert not same["route_miss"][i].any()
    # the order within a position's eight is no matter
    flipped = reference.forward(params, ROWS, MODEL,
                                forced_tail=drv.last_choice(tail)[..., ::-1])
    np.testing.assert_allclose(flipped["logits"], own["logits"],
                               rtol=1e-5, atol=1e-4)
    # a choice by another rule (experts 8-15 whatever the scores):
    # taken over at the last position and nowhere else, and seen to be
    # far off there
    worst = np.stack([np.broadcast_to(np.arange(8, 16), (3, 1, 8))
                      for _ in range(3)], axis=1)
    forced = reference.forward(params, ROWS, MODEL, forced_tail=worst)
    for i in range(3):
        assert (forced["routed"][i][:, -1] == worst[:, i, 0]).all()
        assert (forced["routed"][i][:, :-1] == own["routed"][i][:, :-1]
                ).all()
    missed = np.concatenate([m.ravel()
                             for m in forced["route_miss"].values()])
    assert 0 <= missed.min() and missed.max() <= 8 and missed.sum() > 0
    gaps = np.concatenate([g.ravel()
                           for g in forced["route_gap"].values()])
    assert ((missed > 0) == (gaps > 0)).all()
    # keep_tail keeps the kept layers' last positions alone
    kept = reference.forward(params, ROWS, MODEL, keep_blocks=[0, 2],
                             keep_tail=4)
    assert sorted(kept["operators"]) == [0, 2]
    assert kept["operators"][2].shape == (3, 4, 64)
    whole = reference.forward(params, ROWS, MODEL, keep_blocks=[2])
    np.testing.assert_array_equal(kept["operators"][2],
                                  whole["operators"][2][:, -4:])


# ------------------------------------------------- the cell, at a tiny size

TOY_LIMITS = {"class_gap": 0.3, "logit_rel_l2": 0.05, "route_gap": 0.05,
              "route_miss": 0.1, "swa_rel_l2": 0.02, "full_rel_l2": 0.06}


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
    mix["limits"].update(limits or TOY_LIMITS)
    json.dump(mix, open(path, "w"))
    json.dump(BENCH, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("mellum2"))


@pytest.fixture(scope="module")
def line(root):
    return run.run_cell(root, CELL, 2 ** 31 + 7, 1.5, False,
                        require_tpu=False)


def test_cell_end_to_end(line):
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 30 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] <= \
        line["metrics"]["serve_p95_ms"]["value"]
    assert set(line["compared"]) == {"class_gap", "logit_rel_l2",
                                     "route_gap", "route_miss",
                                     "swa_rel_l2", "full_rel_l2",
                                     "served_not_model", "unanswered"}
    assert 0 < line["compared"]["swa_rel_l2"]["value"] < 0.02
    assert 0 < line["compared"]["full_rel_l2"]["value"] < 0.06
    assert len(line["info"]["rows_rel_l2"]) == 8 == len(
        line["info"]["rows_route_gap"])
    assert line["compared"]["route_gap"]["value"] == max(
        line["info"]["rows_route_gap"])
    assert line["compared"]["served_not_model"]["value"] == 0
    info = line["info"]
    assert info["recompiles"] == 0 and info["sampled"] == 8
    # the model's counters of the window: a row a request, every routed
    # pair held (48 tokens x 8 experts x 8 layers a row)
    assert info["rows_scored"] == 30
    assert info["moe_tokens_held"] == 48 * 8 * 8
    assert info["moe_passes"] == 1.0 and info["weights_cast_leaves"] == 0
    assert info["moe_load_max_over_mean"] >= 1.0


def test_controls_read_not_correct(root):
    """The program reads correct and the reference with one thing
    changed (``control_mellum2.STAND_INS``) in its place does not, each
    by the check that is its own."""
    import control_mellum2
    assert set(control_mellum2.STAND_INS) == {
        "fp8", "no_routed", "no_window", "window_1023", "no_yarn",
        "no_attention_factor", "kv_mod", "sigmoid", "no_renorm"}
    # the toy's window is 8 keys: one short is 7
    stand_ins = {**control_mellum2.STAND_INS, "window_1023": {"window": 7}}
    drv = driver()
    cell = run.load_cell(root, CELL)
    cell["seconds"] = 1.0
    control_mellum2.STAND_INS.update(stand_ins)
    try:
        got = drv.control(cell, 17, ["sound", "unforced", *stand_ins])
    finally:
        control_mellum2.STAND_INS["window_1023"] = {"window": 1023}
    info = got.pop("info")
    value = {name: {c["name"]: c["value"] for c in checks}
             for name, checks in got.items()}
    assert run.judge(got["sound"]), value["sound"]
    assert len(info["rows_rel_l2_sound"]) == 8
    for name in stand_ins:
        assert not run.judge(got[name]), (name, value[name])
        assert len(info[f"rows_rel_l2_{name}"]) == drv.CONTROL_ROWS == 4
    # the window and the default table by layer 0's output ...
    for name in ("no_window", "window_1023", "kv_mod", "fp8"):
        assert value[name]["swa_rel_l2"] > 0.02 \
            > 2 * value["sound"]["swa_rel_l2"], (name, value[name])
    # ... which the full layers' table and the experts do not move
    for name in ("no_yarn", "no_attention_factor", "no_routed", "sigmoid",
                 "no_renorm"):
        assert value[name]["swa_rel_l2"] < 1e-5, (name, value[name])
    # YaRN's table and its factor by layer 3's output
    for name in ("no_yarn", "no_attention_factor"):
        assert value[name]["full_rel_l2"] > 0.1, (name, value[name])
    # the experts' arithmetic by the logits
    for name in ("no_routed", "sigmoid", "no_renorm", "fp8"):
        assert value[name]["logit_rel_l2"] > 0.08 \
            > 4 * value["sound"]["logit_rel_l2"], (name, value[name])
