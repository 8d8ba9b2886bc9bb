"""The ``trinity_score_16k_steady`` cell's own tests: CPU only, a tiny
preset. The configuration's entry and file (with its cuts, the widths
read by this configuration's own keys against the published ones), the
benchmark as it stands with six cells, the yardstick ``flops_trinity``
against hand counts, each new reader by hand on a profile written by
hand and silent with nothing to read, the names the readers find things
by, the driver end to end and the controls of ``correct``.

Six of the checks here are those of benchmark tests that assert the
benchmark of PR 38 (five cells, the per-layer list's end, each generic
reader's ``workloads`` ending in the Mellum2 cell) or uncut
configurations, and are marked expected failures from tests/conftest.py:
see PERF.md, Open questions 0i. Each repeat holds every assertion of the
test it stands for and changes one thing, marked. No topology or TPU
call is made anywhere in this file.
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
CELL = "trinity_score_16k_steady"
MELLUM2_CELL = "mellum2_score_16k_steady"
LFM2_CELL = "lfm2_score_8k_steady"
GLM_CELL = "glm52_score_8k_steady"
CONFIG = "trinity-mini-stage"
MIX = "poisson_steady_16k_trinity"
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_trinity")
import flops_trinity as fl   # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce          # noqa: E402
import xplane_scopes         # noqa: E402

m2 = _load(os.path.join(HERE, "test_mellum2_cell.py"),
           "mellum2_cell_test_for_trinity")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_METRICS = ["trinity_forward_mfu", "trinity_experts_roofline",
               "attn_gate_share", "moe_shared_share"]
# the accepted readers that read this cell as it is (ISSUE 40)
APPENDED = ["serve_queue_wait_ms", "scorer_device_wait_ms",
            "device_idle_serve", "device_idle_serve_named",
            "device_idle_serve_pending", "device_idle_serve_starved",
            "serve_bucket_fill", "serve_token_wait_ms",
            "serve_dispatch_wait_ms", "serve_worker_host_ms",
            "moe_load_max_over_mean", "moe_dispatch_share",
            "swa_attend_share", "swa_flash_roofline",
            "mellum2_flash_roofline"]
# test_occupancy.py's lists, every name of them
MELLUM2_METRICS = ["mellum2_forward_mfu", "mellum2_experts_roofline",
                   "mellum2_flash_roofline", "swa_flash_roofline",
                   "swa_attend_share"]
LFM2_METRICS = ["lfm2_forward_mfu", "lfm2_experts_roofline",
                "lfm2_flash_roofline", "moe_dispatch_share",
                "short_conv_gate_share"]
GLM_METRICS = ["glm_forward_mfu", "dsa_attend_roofline", "dsa_select_share",
               "moe_experts_roofline", "moe_load_max_over_mean"]
GENERIC = ["serve_queue_wait_ms", "scorer_device_wait_ms",
           "device_idle_serve", "serve_token_wait_ms",
           "serve_dispatch_wait_ms", "serve_worker_host_ms",
           "device_idle_serve_named", "moe_load_max_over_mean"]
OCCUPANCY = ["device_idle_serve_pending", "device_idle_serve_starved",
             "serve_bucket_fill"]
# of Mellum2's own readers, those that read this cell too
SHARED_WITH_MELLUM2 = ["mellum2_flash_roofline", "swa_flash_roofline",
                       "swa_attend_share"]
SCOPES = ("attn_gate", "moe_shared", "swa_attend", "gqa_attend",
          "gqa_project", "moe_route", "moe_experts", "moe_grouped",
          "moe_combine", "lm_head_last")
KINDS = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {"vocab_size": 128, "max_len": 48, "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 8, "moe_intermediate_size": 32,
        "intermediate_size": 96, "num_experts": 16}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def body():
    return json.load(open(os.path.join(BENCH_DIR, "configs",
                                       CONFIG + ".json")))


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


def driver():
    return run.load_module(os.path.join(BENCH_DIR, "drivers",
                                        "serve_trinity.py"))


# ------------------------------------------------- BENCHMARK.json and the file

def test_config_entry_and_its_file_with_cuts():
    """``test_benchmark_cells.py::test_config_entry_and_its_file`` with
    ``reduced`` as it stands (the one change) and the widths read by
    this configuration's own keys."""
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG for w in BENCH["workloads"])
    b = body()
    assert b["source"] == config["source"] == SOURCE
    assert b["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    # the published values of what was cut stand beside the cut ones
    pub = b["published"]
    assert pub["num_hidden_layers"] == 32 == len(pub["layer_types"])
    assert pub["layer_types"] == PERIOD * 8 and pub["num_dense_layers"] == 2
    # published layer 1 (the leading dense layers counted once) and
    # layers 2-5: one whole period of expert layers, 3 sliding to 1 full
    assert b["num_hidden_layers"] == 5 and b["num_dense_layers"] == 1
    assert b["layer_types"] == pub["layer_types"][1:6] == KINDS
    assert sorted(b["layer_types"][1:]) == sorted(PERIOD)
    spec = b["networkSpec"]
    assert spec["type"] == "hybrid_moe_lm"
    assert spec["layer_types"] == b["layer_types"]
    assert spec["num_dense_layers"] == b["num_dense_layers"]
    assert len(spec["layer_types"]) == b["num_hidden_layers"]
    # no width is cut: the file's published keys and what is run agree
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts", "num_experts_per_tok",
                "num_shared_experts", "vocab_size", "sliding_window",
                "rope_theta", "mup_enabled", "tie_word_embeddings"):
        assert spec[key] == b[key], key
    assert spec["norm_eps"] == b["rms_norm_eps"] == 1e-5
    assert (spec["routed_scaling_factor"], spec["scoring_func"]) == (
        b["route_scale"], b["score_func"]) == (2.826, "sigmoid")
    assert (b["hidden_size"], b["head_dim"], b["intermediate_size"],
            b["moe_intermediate_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["num_experts"],
            b["num_experts_per_tok"], b["num_shared_experts"],
            b["vocab_size"], b["sliding_window"], b["rope_theta"]) == (
        2048, 128, 6144, 1024, 32, 4, 128, 8, 1, 200192, 2048, 10000)
    assert (b["global_attn_every_n_layers"], b["n_group"], b["topk_group"],
            b["num_expert_groups"], b["num_limited_groups"],
            b["load_balance_coeff"], b["max_position_embeddings"]) == (
        4, 1, 1, 1, 1, 0.001, 131072)
    assert (b["route_norm"], b["mup_enabled"], b["use_grouped_mm"],
            b["tie_word_embeddings"], b["rope_scaling"]) == (
        True, True, True, False, None)
    assert b["model_type"] == "afmoe" and b["hidden_act"] == "silu"
    # what the config has no key for is the family's, each stated
    assert (spec["attention_output_gate"], spec["sandwich_norms"],
            spec["use_expert_bias"], spec["gate_norm_eps"]) == (
        True, True, True, 1e-20)
    assert spec["rope_parameters"] == {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
        "full_attention": {"rope_type": "none"}}
    assert set(b["assumed"]) >= {
        "output_gate", "qk_norm", "rope", "norms", "embedding_scale",
        "shared_expert", "router", "window", "max_len", "initial_weights"}
    dep = b["deployment"]
    assert dep["pipeline_stages"] == 8 and sum(dep["layers_a_stage"]) == 32
    assert "2048 tokens an expert" in dep["tokens_an_expert_a_step"]
    assert b["departures"] and b["precision"]
    assert b["parameters"] == fl.parameters(spec) == 4_241_534_720
    assert b["parameter_bytes"] == 2 * b["parameters"]
    assert spec["max_len"] == 16384


def test_the_cell_and_what_it_reports():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "chips": 1, "traffic": MIX}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(APPENDED + NEW_METRICS)
    assert len(APPENDED) == 15 and set(APPENDED) == set(
        GENERIC + OCCUPANCY + ["moe_dispatch_share"] + SHARED_WITH_MELLUM2)
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [(entries[n]["better"], entries[n]["layer"])
            for n in NEW_METRICS] == [
        ("higher", "model step"), ("higher", "kernels"),
        ("lower", "model step"), ("lower", "experts")]
    for name in NEW_METRICS:
        assert entries[name] == {
            "name": name, "unit": "%", "better": entries[name]["better"],
            "source": "device_trace", "layer": entries[name]["layer"],
            "moves": "serve_p95_ms", "workloads": [CELL]}
    for name in APPENDED:
        assert entries[name]["workloads"][-1] == CELL
        assert entries[name]["workloads"].count(CELL) == 1
    # the other families' step readers stay theirs
    for name, m in entries.items():
        if name not in APPENDED + NEW_METRICS:
            assert CELL not in m["workloads"], name
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_trinity" and mix["batch_size"] == 2
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
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.2
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    assert 0 < mix["limits"]["swa_rel_l2"] \
        <= mix["limits"]["full_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


# ---- the checks of the benchmark's tests that pin it as PR 38 left it,
# ---- as it stands now (tests/conftest.py marks the originals)

def test_the_three_entries_as_issue_38_asks():
    """``test_occupancy.py::test_the_three_entries_as_issue_38_asks`` as
    the benchmark stands, every assertion of it, changed in one place:
    each entry's ``workloads`` has this cell appended, and a cell's own
    per-layer list ends with the three only up to PR 38's cells."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    serve_cells = ["gpt2xl_serve_steady", GLM_CELL, LFM2_CELL, MELLUM2_CELL]
    assert [(entries[n]["better"], entries[n]["layer"])
            for n in OCCUPANCY] == [
        ("lower", "serving"), ("lower", "device"), ("higher", "scorer")]
    for name in OCCUPANCY:
        m = entries[name]
        assert m == {"name": name, "unit": "%", "better": m["better"],
                     "source": "program_span", "layer": m["layer"],
                     "moves": "serve_p95_ms",
                     "workloads": serve_cells + [CELL]}   # the one change
    for cell in serve_cells:
        loaded = run.load_cell(ROOT, cell)
        assert [m["name"] for m in loaded["per_layer"]][-3:] == OCCUPANCY
    # ... and in this cell the four new readers follow them
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["per_layer"]][-7:] == \
        OCCUPANCY + NEW_METRICS
    train = run.load_cell(ROOT, "gpt2m_train")
    assert not set(OCCUPANCY) & {m["name"] for m in train["per_layer"]}


def test_the_mellum2_cell_and_what_it_reports():
    """``test_occupancy.py::test_the_mellum2_cell_and_what_it_reports``
    as the benchmark stands, every assertion of it, changed in one
    place: the readers this cell shares are no longer the Mellum2 cell's
    alone, and their lists end with this cell after it."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == MELLUM2_CELL)
    assert cell == {**cell, "config": "mellum2-12b-a2.5b-stage", "chips": 1,
                    "traffic": "poisson_steady_16k_mellum2"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, MELLUM2_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + ["moe_dispatch_share"] + MELLUM2_METRICS
               + OCCUPANCY)
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline",
                         "lfm2_forward_mfu", "lfm2_experts_roofline",
                         "lfm2_flash_roofline", "short_conv_gate_share"):
            assert MELLUM2_CELL not in m["workloads"]
        if m["name"] in MELLUM2_METRICS:
            assert m["workloads"] == (        # the one change: shared
                [MELLUM2_CELL, CELL] if m["name"] in SHARED_WITH_MELLUM2
                else [MELLUM2_CELL]) and m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC + ["moe_dispatch_share"]:
            assert m["workloads"][-2:] == [MELLUM2_CELL, CELL]  # and here
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in MELLUM2_METRICS] == [
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
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.2
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    assert 0 < mix["limits"]["swa_rel_l2"] \
        <= mix["limits"]["full_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


def test_the_lfm2_cell_reports_what_it_did():
    """``test_occupancy.py::test_the_lfm2_cell_reports_what_it_did`` as
    the benchmark stands, every assertion of it, changed in one place:
    ``moe_dispatch_share`` and the generic readers list this cell after
    the Mellum2 cell."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == LFM2_CELL)
    assert cell == {**cell, "config": "lfm2-24b-a2b-stage", "chips": 1,
                    "traffic": "poisson_steady_8k_lfm2"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, LFM2_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + LFM2_METRICS + OCCUPANCY)
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline"):
            assert LFM2_CELL not in m["workloads"]
        if m["name"] in LFM2_METRICS:
            assert m["workloads"] == (      # the one change: this cell
                [LFM2_CELL, MELLUM2_CELL, CELL]
                if m["name"] == "moe_dispatch_share" else [LFM2_CELL])
            assert m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC:            # ... and at these lists' end
            assert m["workloads"][-3:] == [LFM2_CELL, MELLUM2_CELL, CELL]
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


def test_the_glm_cell_reports_what_it_did():
    """``test_occupancy.py::test_the_glm_cell_reports_what_it_did`` as
    the benchmark stands, every assertion of it, changed in one place:
    ``moe_load_max_over_mean`` lists this cell after the other three."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == GLM_CELL)
    assert cell == {**cell, "config": "glm-5.2-ep16", "chips": 1,
                    "traffic": "poisson_steady_8k"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, GLM_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == \
        GENERIC[:-1] + GLM_METRICS + OCCUPANCY
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline"):
            assert GLM_CELL not in m["workloads"]
        if m["name"] in GLM_METRICS:
            assert m["workloads"] == (      # the one change: this cell
                [GLM_CELL, LFM2_CELL, MELLUM2_CELL, CELL]
                if m["name"] == "moe_load_max_over_mean"
                else [GLM_CELL])
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
    """``test_occupancy.py::test_benchmark_json_is_still_well_formed`` as
    the benchmark stands, every assertion of it, changed in one place: a
    sixth cell and configuration, and four more per-layer entries at the
    list's end."""
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", GLM_CELL,
                     LFM2_CELL, MELLUM2_CELL, CELL]     # the one change
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)
    assert [c["name"] for c in BENCH["configs"]] == [
        "gpt2-medium", "gpt2-xl", "glm-5.2-ep16", "lfm2-24b-a2b-stage",
        "mellum2-12b-a2.5b-stage", CONFIG]              # ... and here
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 40
    assert [m["name"] for m in BENCH["per_layer"]][-17:] == \
        LFM2_METRICS + MELLUM2_METRICS + OCCUPANCY + NEW_METRICS  # and here
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"train_tokens_per_s": 0.01, "serve_p50_ms": 0.03,
                      "serve_p95_ms": 0.07, "setup_s": 0.1}
    # what a PR that adds a cell may not touch: the parent's entries are
    # there as they were, this cell's name appended and nothing else
    serve = {m["name"]: m["workloads"] for m in BENCH["end_to_end"]
             if "workloads" in m}
    assert serve["serve_p50_ms"] == serve["serve_p95_ms"] == [
        "gpt2xl_serve_steady", GLM_CELL, LFM2_CELL, MELLUM2_CELL, CELL]
    assert serve["train_tokens_per_s"] == ["gpt2m_train"]


# ------------------------------------------------------------- the yardstick

def test_flops_at_the_cell_s_size():
    """ISSUE 40's arithmetic, checked against the tree."""
    spec = body()["networkSpec"]
    assert fl.gate_params(spec) == 2048 * 32 * 128 == 8_388_608
    assert fl.attention_params(spec) == 27_263_232
    assert fl.norm_params(spec) == 8_192
    assert 3 * 2048 * 6144 == 37_748_736
    assert fl.dense_layer_params(spec) == 65_020_160
    assert fl.expert_params(spec) == fl.shared_params(spec) == 6_291_456
    assert 128 * fl.expert_params(spec) == 805_306_368
    assert fl.expert_layer_params(spec) == 839_131_520
    assert 200192 * 2048 == 409_993_216
    assert fl.expert_layers(spec) == 4
    assert fl.parameters(spec) == 4_241_534_720                # 8.48 GB
    assert fl.banded_pairs(16384, 2048) == 31_458_304
    assert fl.causal_pairs(16384) == 134_225_920
    parts = fl.parts_per_row(spec, 16384)
    per_row = fl.forward_flops_per_row(spec, 16384)
    assert per_row == pytest.approx(17.42e12, rel=1e-3)
    assert per_row == sum(parts.values())
    assert fl.expected_pairs(spec, 16384) == 16384 * 8 * 4 == 524_288
    share = {k: v / per_row for k, v in parts.items()}
    assert share["routed"] == pytest.approx(0.38, abs=0.005)
    # the five projections a layer, the gate's among them
    assert share["projections"] + share["gate_projection"] \
        == pytest.approx(0.26, abs=0.005)
    assert parts["gate_projection"] == pytest.approx(5 * 0.275e12, rel=2e-3)
    assert share["gate_projection"] == pytest.approx(0.079, abs=0.001)
    assert share["causal_pairs"] == pytest.approx(0.126, abs=0.001)
    assert share["banded_pairs"] == pytest.approx(0.118, abs=0.001)
    assert share["dense"] == pytest.approx(0.071, abs=0.001)
    assert share["shared"] == pytest.approx(0.047, abs=0.001)
    assert share["routed"] + share["shared"] + share["gate_projection"] \
        + share["banded_pairs"] + share["causal_pairs"] \
        == pytest.approx(0.75, abs=0.01)
    # without the window the same row needs 24.2 TFLOP: it removes 28%
    every = fl.forward_flops_per_row(
        {**spec, "layer_types": ["full_attention"] * 5}, 16384)
    assert every == pytest.approx(24.16e12, rel=1e-3)
    assert 1 - per_row / every == pytest.approx(0.28, abs=0.005)
    # the program's count of pairs in the place of 8 a token
    assert fl.forward_flops_per_row(spec, 16384, 524_288.0) == per_row
    assert fl.forward_flops_per_row(spec, 16384, 0.0) == pytest.approx(
        per_row - parts["routed"])
    # a fifth expert layer, were it asked for
    assert fl.parameters({**spec, "layer_types": KINDS + [
        "sliding_attention"]}) == 5_080_666_240
    # the older keys: no gate, two norms, no shared expert, no bias
    old = {**spec, "attention_output_gate": False, "sandwich_norms": False,
           "num_shared_experts": 0, "use_expert_bias": False}
    assert fl.attention_params(old) == 27_263_232 - 8_388_608
    assert fl.expert_layer_params(old) == 839_131_520 - 8_388_608 - 4096 \
        - 6_291_456 - 128


# --------------------------------------------- the readers on a written profile

_J = m2._J
SWA, GQA, GMM, at = m2.SWA, m2.GQA, m2.GMM, m2.at
# an execution: four sliding layers' flash calls and a copy beside one,
# the full layer's call, the gate's fusion a layer (5), a grouped call
# and what surrounds it, the shared expert's two fusions, the rest
OPS = {
    SWA: (_J + "layer_0_attn/swa_attend/jit(_flash_forward)/pallas_call:",
          at(10.0, 0.6, 4, 0.5)),
    "%copy.9 = bf16[2,32,16384,128] copy(%q)": (
        _J + "layer_0_attn/swa_attend/transpose:", at(12.4, 1.0, 1, 0.4)),
    GQA: (_J + "layer_2_attn/gqa_attend/jit(_flash_forward)/pallas_call:",
          at(12.8, 1.6, 1, 1.5)),
    "%fusion.30 = bf16[2,16384,32,128] fusion(%u, %w, %o)": (
        _J + "layer_0_attn/attn_gate/mul:", at(14.4, 0.3, 5, 0.25)),
    GMM: (_J + "layer_1_moe/moe_experts/while/body/moe_grouped/"
          "jit(_moe_grouped_matmul)/jit(gmm)/pallas_call:",
          at(16.0, 1.0, 1, 1.0)),
    "%fusion.9 = bf16[32768,2048] fusion(%d)": (
        _J + "layer_1_moe/moe_experts/while/body/gather:",
        at(17.0, 1.0, 1, 0.7)),
    "%fusion.12 = f32[32768,2048] fusion(%e)": (
        _J + "layer_1_moe/moe_experts/moe_combine/gather:",
        at(17.7, 1.0, 1, 0.8)),
    "%fusion.40 = bf16[32768,1024] fusion(%u, %g, %p)": (
        _J + "layer_1_moe/moe_shared/shared_0/mul:", at(18.5, 1.0, 1, 0.4)),
    "%fusion.41 = f32[32768,2048] fusion(%h, %d)": (
        _J + "layer_1_moe/moe_shared/shared_0/tn,nk->tk/dot_general:",
        at(18.9, 1.0, 1, 0.2)),
    "%copy.3 = f32[8] copy(%e)": (None, at(19.1, 1.0, 1, 4.0)),
}
MAIN_RUNS = [(10.0, 20.0), (40.0, 20.0)]
BUSY_MS = 2 * (4 * 0.5 + 0.4 + 1.5 + 5 * 0.25 + 1.0 + 0.7 + 0.8 + 0.4 + 0.2
               + 4.0)


def context(tmp_path, ops=None, runs=None):
    xplane_scopes.device_metadata.cache_clear()
    trace_dir = m2.write_profile(tmp_path / ".bench_trace" / CELL,
                                 OPS if ops is None else ops,
                                 MAIN_RUNS if runs is None else runs)
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    reduced = trace_reduce.reduce_trace(trace_dir)
    return {"cell": cell, "trace": reduced, "peak": PEAK,
            "counters": {"rows_ok": 7, "seq": 16384, "bucket": 2,
                         "batch_rows": 1.5, "moe_tokens_held": 524288.0,
                         "moe_load_max_over_mean": 1.4,
                         "moe_passes": 8.0}}


def least(cost):
    return max(cost["flops"] / 197e12, cost["bytes"] / 819e9)


def test_new_readers_by_hand(tmp_path):
    ctx = context(tmp_path)
    spec = ctx["cell"]["config_file"]["networkSpec"]
    t = ctx["trace"]
    busy = BUSY_MS / 1e3
    assert t["module_runs"] == 2 and t["busy_s"] == pytest.approx(busy)
    need = fl.forward_flops_per_row(spec, 16384, 524288.0) * 7
    assert reader("trinity_forward_mfu").read(ctx) == pytest.approx(
        100 * need / (busy * 197e12))
    # the grouped products: the custom calls under moe_experts (1 ms an
    # execution) against the *four* expert layers' least time, the
    # per-row pairs a layer 524,288 / 4 at 1.5 rows a bucket
    pairs = 524288.0 / 4 * 1.5
    assert reader("trinity_experts_roofline").read(ctx) == pytest.approx(
        100 * least(fl.experts_cost(spec, pairs)) * 4 * 2 / 0.002)
    # five fusions an execution under attn_gate, two under moe_shared
    assert reader("attn_gate_share").read(ctx) == pytest.approx(
        100 * 2 * 5 * 0.25e-3 / busy)
    assert reader("moe_shared_share").read(ctx) == pytest.approx(
        100 * 2 * 0.6e-3 / busy)
    # ... and the accepted readers read the cell as it is: one call a
    # sliding layer (4) and a full layer (1) an execution
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100 * 8 * least(fl.flash_cost(
            spec, "sliding_attention", 2, 16384)) / 0.004)
    assert reader("mellum2_flash_roofline").read(ctx) == pytest.approx(
        100 * 2 * least(fl.flash_cost(
            spec, "full_attention", 2, 16384)) / 0.003)
    assert reader("swa_attend_share").read(ctx) == pytest.approx(
        100 * 2 * 2.4e-3 / busy)
    # under moe_experts 2.5 ms an execution, 1.0 of it under moe_grouped:
    # the shared expert is under neither
    assert reader("moe_dispatch_share").read(ctx) == pytest.approx(
        100 * 2 * 1.5e-3 / busy)
    assert reader("moe_load_max_over_mean").read(ctx) == 1.4


def test_no_new_reader_reads_over_a_hundred(tmp_path):
    """A full bucket at the chip's peak reads 100 at most: the needed
    work of real rows over a trace in which every kernel runs at its
    roofline."""
    spec = body()["networkSpec"]
    step_ms = 1e3 * 2 * fl.forward_flops_per_row(spec, 16384) / 197e12
    gmm_ms = 1e3 * least(fl.experts_cost(spec, 2 * 16384 * 8))
    parts = fl.parts_per_row(spec, 16384)
    gate_ms = 1e3 * 2 * parts["gate_projection"] / 197e12
    shared_ms = 1e3 * 2 * parts["shared"] / 197e12
    used = 4 * gmm_ms + gate_ms + shared_ms
    ops = {GMM: (OPS[GMM][0], at(0.0, gmm_ms, 4, gmm_ms, (0.0,))),
           "%fusion.30 = bf16[2,16384,32,128] fusion(%u, %w, %o)": (
               _J + "layer_0_attn/attn_gate/mul:",
               [(4 * gmm_ms, gate_ms)]),
           "%fusion.40 = bf16[32768,1024] fusion(%u, %g, %p)": (
               _J + "layer_1_moe/moe_shared/shared_0/mul:",
               [(4 * gmm_ms + gate_ms, shared_ms)]),
           "%fusion.1 = f32[8] fusion(%p)": (
               _J + "layer_0_attn/gqa_project/dot_general:",
               [(used, step_ms - used)])}
    ctx = context(tmp_path, ops, [(0.0, step_ms)])
    ctx["counters"].update(rows_ok=2, batch_rows=2.0)
    assert ctx["trace"]["module_runs"] == 1
    assert ctx["trace"]["busy_s"] == pytest.approx(step_ms / 1e3, rel=1e-6)
    for name in NEW_METRICS[:2]:
        assert reader(name).read(ctx) == pytest.approx(100.0, rel=1e-6), name
    # the two shares at their needed part of the step, and under 100
    assert reader("attn_gate_share").read(ctx) == pytest.approx(7.9, abs=0.1)
    assert reader("moe_shared_share").read(ctx) == pytest.approx(4.7,
                                                                 abs=0.1)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    """An untraced context, a profile without the scopes or the counters
    (the parent's program on this cell, GLM's, LFM2's, Mellum2's): None,
    and nothing raised."""
    xplane_scopes.device_metadata.cache_clear()
    read = reader(name).read
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    assert read({"cell": cell, "trace": None, "peak": None,
                 "counters": {}}) is None
    host_spans_test = _load(os.path.join(HERE, "test_host_spans.py"),
                            "host_spans_test_for_trinity")
    host_spans_test.write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    ctx = {"cell": cell, "trace": reduced, "peak": PEAK,
           "counters": {"seq": 16384, "bucket": 2, "rows_ok": 5,
                        "batch_rows": 1.5}}
    assert read(ctx) is None
    for other, tag in (("test_glm_dsa_cell.py", "glm"),
                       ("test_lfm2_cell.py", "lfm2"),
                       ("test_mellum2_cell.py", "mellum2")):
        module = _load(os.path.join(HERE, other), f"{tag}_profile_for_tr")
        xplane_scopes.device_metadata.cache_clear()
        shutil.rmtree(tmp_path / ".bench_trace")
        module.write_profile(tmp_path / ".bench_trace" / CELL)
        ctx["trace"] = trace_reduce.reduce_trace(
            str(tmp_path / ".bench_trace" / CELL))
        if name == "moe_shared_share" and tag == "glm":
            continue            # GLM's program names a moe_shared scope
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
    # the gate lies in every attention operator, the dense layer's too,
    # inside neither gqa_project nor an attend scope
    for i in range(5):
        assert re.search(rf"layer_{i}_attn/attn_gate/", text), i
    assert not re.search(r"(gqa_project|swa_attend|gqa_attend)/"
                         r"([a-z_]+/)*attn_gate", text)
    assert not re.search(r"attn_gate/([a-z_]+/)*(gqa_project|swa_attend"
                         r"|gqa_attend)", text)
    # the full layer is cut layer 2; no expert layer at layer 0
    assert re.search(r"layer_2_attn/gqa_attend/", text)
    assert re.search(r"layer_0_attn/swa_attend/", text)
    assert not re.search(r"layer_2_attn/swa_attend/", text)
    assert not re.search(r"layer_0_moe", text)
    for i in range(1, 5):
        assert re.search(rf"layer_{i}_moe/moe_shared/shared_0/", text), i
    assert not re.search(r"moe_experts/([a-z_()]+/)*moe_shared", text)
    assert re.search(r"moe_experts/(while/body/)?(closed_call/)?"
                     r"moe_grouped/", text)
    assert re.search(r"moe_experts/moe_combine/", text)
    assert driver().ROW_STATS == tuple(module.row_stats) == (
        "moe_tokens_held", "moe_load_max_over_mean", "moe_passes")
    assert run.load_module(os.path.join(
        BENCH_DIR, "drivers", "serve_hybrid_lm.py")).TAILS == tuple(
        module.row_outputs)
    # the three counters, from the spec
    assert (module.attn_gated_layers, module.rope_free_layers,
            module.moe_shared_experts) == (5, 1, 1)
    full = build_network({"dtype": "bfloat16", **body()["networkSpec"]})
    assert (full.attn_gated_layers, full.rope_free_layers,
            full.moe_shared_experts, full.moe_gather_combines) == (5, 1, 1, 4)


def test_reference_and_yardstick_import_nothing_of_the_program():
    for name in ("reference_trinity.py", "flops_trinity.py",
                 "trace_trinity.py", "control_trinity.py"):
        text = open(os.path.join(BENCH_DIR, name)).read()
        assert not re.search(r"^\s*(from|import) mmlspark_tpu", text,
                             re.M), name
    for name in NEW_METRICS:
        text = open(os.path.join(BENCH_DIR, "metrics", name + ".py")).read()
        assert "mmlspark_tpu" not in text and "min(" not in text, name
    text = open(os.path.join(BENCH_DIR, "reference_trinity.py")).read()
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "ragged_dot" not in text
    assert "float32" in text and "bfloat16" not in text.split('"""', 2)[2]


def test_the_reference_takes_a_program_s_choices_at_the_last_position():
    import numpy as np
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from trinity_tiny import ROWS, TINY as MODEL, build, reference
    _, params = build()
    drv = driver()
    own = reference.forward(params, ROWS, MODEL)
    # the dense layer routes nothing: the expert layers alone
    assert sorted(own["routed"]) == [1, 2] == sorted(own["route_gap"])
    assert own["route_gap"][1].shape == (3, 0)          # nothing forced
    tail = drv.tail_of(own, 16)
    assert tail.shape == (3, 2, 16, 4)
    same = reference.forward(params, ROWS, MODEL,
                             forced_tail=drv.last_choice(tail))
    np.testing.assert_allclose(same["logits"], own["logits"], rtol=1e-6)
    for i in (1, 2):            # every forced position is looked at
        assert same["route_gap"][i].shape == (3, 1) \
            == same["route_miss"][i].shape
        assert not same["route_gap"][i].any()
        assert not same["route_miss"][i].any()
    # a choice by another rule (experts 8-11 whatever the scores): taken
    # over at the last position of each expert layer and nowhere else
    worst = np.stack([np.broadcast_to(np.arange(8, 12), (3, 1, 4))
                      for _ in range(2)], axis=1)
    forced = reference.forward(params, ROWS, MODEL, forced_tail=worst)
    for j, i in enumerate((1, 2)):
        assert (forced["routed"][i][:, -1] == worst[:, j, 0]).all()
        assert (forced["routed"][i][:, :-1] == own["routed"][i][:, :-1]
                ).all()
    missed = np.concatenate([m.ravel()
                             for m in forced["route_miss"].values()])
    gaps = np.concatenate([g.ravel()
                           for g in forced["route_gap"].values()])
    assert 0 <= missed.min() and missed.max() <= 4 and missed.sum() > 0
    assert ((missed > 0) == (gaps > 0)).all()
    kept = reference.forward(params, ROWS, MODEL, keep_blocks=[0, 2],
                             keep_tail=4)
    assert sorted(kept["operators"]) == [0, 2]
    assert kept["operators"][2].shape == (3, 4, 64)
    assert drv.held_layers(MODEL) == {"swa_rel_l2": 0, "full_rel_l2": 2}
    assert drv.held_layers(body()["networkSpec"]) == {
        "swa_rel_l2": 0, "full_rel_l2": 2}
    with pytest.raises(TypeError):
        reference.forward(params, ROWS, MODEL, yarn=False)


# ------------------------------------------------- the cell, at a tiny size

TOY_LIMITS = {"class_gap": 0.3, "logit_rel_l2": 0.05, "route_gap": 0.02,
              "route_miss": 0.03, "swa_rel_l2": 0.02, "full_rel_l2": 0.06}


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
    return make_root(tmp_path_factory.mktemp("trinity"))


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
    # pair held (48 tokens x 8 experts x 4 expert layers a row)
    assert info["rows_scored"] == 30
    assert info["moe_tokens_held"] == 48 * 8 * 4
    assert info["moe_passes"] == 1.0 and info["weights_cast_leaves"] == 0
    assert info["moe_load_max_over_mean"] >= 1.0


def test_controls_read_not_correct(root):
    """The program reads correct and the reference with one thing
    changed (``control_trinity.STAND_INS``) in its place does not, each
    by the check that is its own."""
    import control_trinity
    assert set(control_trinity.STAND_INS) == {
        "fp8", "no_routed", "no_shared", "no_gate", "gate_raw", "full_rope",
        "no_sliding_rope", "no_window", "window_2047", "no_post_norms",
        "no_embed_scale", "no_route_scale", "no_renorm", "softmax",
        "no_bias", "kv_mod"}
    # the toy's window is 8 keys: one short is 7
    stand_ins = {**control_trinity.STAND_INS, "window_2047": {"window": 7}}
    drv = driver()
    cell = run.load_cell(root, CELL)
    cell["seconds"] = 1.0
    control_trinity.STAND_INS.update(stand_ins)
    try:
        got = drv.control(cell, 17, ["sound", "unforced", *stand_ins])
    finally:
        control_trinity.STAND_INS["window_2047"] = {"window": 2047}
    info = got.pop("info")
    value = {name: {c["name"]: c["value"] for c in checks}
             for name, checks in got.items()}
    assert run.judge(got["sound"]), value["sound"]
    assert len(info["rows_rel_l2_sound"]) == 8
    for name in stand_ins:
        assert not run.judge(got[name]), (name, value[name])
        assert len(info[f"rows_rel_l2_{name}"]) == drv.CONTROL_ROWS == 2
    # the window, the sliding layers' table, the gate and the heads'
    # grouping by layer 0's output ...
    for name in ("no_window", "window_2047", "no_sliding_rope", "no_gate",
                 "kv_mod", "fp8"):
        assert value[name]["swa_rel_l2"] > 0.02 \
            > 2 * value["sound"]["swa_rel_l2"], (name, value[name])
    # ... which what comes after layer 0's operator does not move
    for name in ("full_rope", "no_routed", "no_shared", "no_post_norms",
                 "no_route_scale", "no_renorm", "softmax", "no_bias"):
        assert value[name]["swa_rel_l2"] < 1e-5, (name, value[name])
    # the norm before the operator takes a scale out of layer 0
    assert value["no_embed_scale"]["swa_rel_l2"] < 0.01
    # that the full layer has no table, and the gate's input, by layer
    # 2's output
    for name in ("full_rope", "gate_raw", "no_gate"):
        assert value[name]["full_rel_l2"] > 0.1, (name, value[name])
    # the experts' and the residual stream's arithmetic by the logits
    for name in ("no_routed", "no_shared", "no_post_norms",
                 "no_embed_scale", "no_route_scale", "no_renorm", "softmax",
                 "fp8"):
        assert value[name]["logit_rel_l2"] > 0.08 \
            > 4 * value["sound"]["logit_rel_l2"], (name, value[name])
    # the bias moves few choices: held by how far they lie from the
    # reference's own
    assert value["no_bias"]["route_gap"] > 0.02 \
        > 4 * value["sound"]["route_gap"]
