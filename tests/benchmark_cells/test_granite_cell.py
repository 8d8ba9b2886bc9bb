"""The ``granite_score_1k_steady`` cell's own tests: CPU only, a tiny
preset. The configuration's entry and file (nothing cut: the widths
read by this configuration's own keys against the published ones), the
benchmark as it stands with seven cells, the yardstick ``flops_granite``
against hand counts, each new reader by hand on a profile written by
hand and silent with nothing to read, the names the readers find things
by, the driver end to end and the controls of ``correct``.

Six of the checks here are those of benchmark tests that assert the
benchmark as the Trinity cell left it (six cells, the per-layer list's end, each generic
reader's ``workloads`` ending in the Trinity cell) or GPT-2's keys for
every configuration, and are marked expected failures from
tests/conftest.py: see PERF.md, Open questions 0i. Each repeat holds
every assertion of the test it stands for and changes one thing,
marked. No topology or TPU call is made anywhere in this file.
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
CELL = "granite_score_1k_steady"
TRINITY_CELL = "trinity_score_16k_steady"
MELLUM2_CELL = "mellum2_score_16k_steady"
LFM2_CELL = "lfm2_score_8k_steady"
GLM_CELL = "glm52_score_8k_steady"
CONFIG = "granite-4.0-h-micro"
MIX = "poisson_steady_1k_granite"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_granite")
import flops_granite as fl   # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce          # noqa: E402
import xplane_scopes         # noqa: E402

m2 = _load(os.path.join(HERE, "test_mellum2_cell.py"),
           "mellum2_cell_test_for_granite")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_METRICS = ["granite_forward_mfu", "ssm_scan_roofline", "ssm_scan_share",
               "ssm_conv_norm_share"]
# the accepted readers that read this cell as they are
APPENDED = ["serve_queue_wait_ms", "scorer_device_wait_ms",
            "device_idle_serve", "device_idle_serve_named",
            "device_idle_serve_pending", "device_idle_serve_starved",
            "serve_bucket_fill", "serve_token_wait_ms",
            "serve_dispatch_wait_ms", "serve_worker_host_ms",
            "mellum2_flash_roofline"]
# test_trinity_cell.py's lists, every name of them
TRINITY_METRICS = ["trinity_forward_mfu", "trinity_experts_roofline",
                   "attn_gate_share", "moe_shared_share"]
TRINITY_APPENDED = ["serve_queue_wait_ms", "scorer_device_wait_ms",
                    "device_idle_serve", "device_idle_serve_named",
                    "device_idle_serve_pending", "device_idle_serve_starved",
                    "serve_bucket_fill", "serve_token_wait_ms",
                    "serve_dispatch_wait_ms", "serve_worker_host_ms",
                    "moe_load_max_over_mean", "moe_dispatch_share",
                    "swa_attend_share", "swa_flash_roofline",
                    "mellum2_flash_roofline"]
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
SHARED_WITH_MELLUM2 = ["mellum2_flash_roofline", "swa_flash_roofline",
                       "swa_attend_share"]
SCOPES = ("ssm_mixer", "ssm_conv", "ssm_scan", "ssm_gated_norm",
          "gqa_project", "gqa_attend", "lm_head_last")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
KINDS = ["full_attention" if k == "attention" else k for k in PERIOD * 4]
TINY = {"vocab_size": 128, "max_len": 37, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "attention_multiplier": 0.0625, "intermediate_size": 96,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 8,
        "layer_types": ["mamba", "mamba", "full_attention", "mamba"],
        "num_dense_layers": 4}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def body():
    return json.load(open(os.path.join(BENCH_DIR, "configs",
                                       CONFIG + ".json")))


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


def driver():
    return run.load_module(os.path.join(BENCH_DIR, "drivers",
                                        "serve_granite.py"))


# ------------------------------------------------- BENCHMARK.json and the file

def test_config_entry_and_its_file_with_its_own_keys():
    """``test_benchmark_cells.py::test_config_entry_and_its_file`` with
    the widths and the depth read by this configuration's own keys (the
    one change: GPT-2's keys are not this family's)."""
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", config["name"])
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG for w in BENCH["workloads"])
    b = body()
    assert b["source"] == config["source"] == SOURCE
    assert b["reduced"] == config["reduced"] == []
    spec = b["networkSpec"]
    # no width or depth differs from the published config
    assert (spec["hidden_size"], len(spec["layer_types"]),
            spec["num_attention_heads"], spec["vocab_size"]) == (
        b["hidden_size"], b["num_hidden_layers"], b["num_attention_heads"],
        b["vocab_size"])
    assert b["departures"] and b["precision"] and b["published"]


def test_the_file_holds_the_published_config():
    """Every number of the published config under its own key (nothing
    cut), the mapping onto ``networkSpec``, and what the config has no
    key for stated."""
    b = body()
    assert (b["num_hidden_layers"], b["hidden_size"], b["intermediate_size"],
            b["shared_intermediate_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["vocab_size"],
            b["max_position_embeddings"]) == (
        40, 2048, 8192, 8192, 32, 8, 100352, 131072)
    assert (b["mamba_n_heads"], b["mamba_d_head"], b["mamba_d_state"],
            b["mamba_n_groups"], b["mamba_d_conv"], b["mamba_expand"],
            b["mamba_chunk_size"], b["mamba_conv_bias"],
            b["mamba_proj_bias"]) == (64, 64, 128, 1, 4, 2, 256, True, False)
    assert (b["embedding_multiplier"], b["residual_multiplier"],
            b["logits_scaling"], b["attention_multiplier"]) == (
        12, 0.22, 8, 0.015625)
    assert (b["num_local_experts"], b["num_experts_per_tok"],
            b["rms_norm_eps"], b["rope_theta"], b["rope_scaling"]) == (
        0, 0, 1e-5, 10000, None)
    assert (b["model_type"], b["position_embedding_type"], b["hidden_act"],
            b["normalization_function"], b["attention_bias"],
            b["tie_word_embeddings"]) == (
        "granitemoehybrid", "nope", "silu", "rmsnorm", False, True)
    assert b["layer_types"] == PERIOD * 4
    assert [i for i, k in enumerate(b["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    spec = b["networkSpec"]
    assert spec["type"] == "hybrid_moe_lm" and spec["layer_types"] == KINDS
    assert spec["num_dense_layers"] == 40 and spec["num_experts"] == 0
    assert spec["intermediate_size"] == b["shared_intermediate_size"]
    assert spec["head_dim"] == 2048 // 32 == 64
    assert spec["rope_parameters"] == {"full_attention": {"rope_type":
                                                          "none"}}
    assert spec["norm_eps"] == b["rms_norm_eps"]
    assert spec["attention_qk_norm"] is False
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "vocab_size", "embedding_multiplier", "residual_multiplier",
                "logits_scaling", "attention_multiplier", "rope_theta",
                "tie_word_embeddings", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
                "mamba_proj_bias"):
        assert spec[key] == b[key], key
    assert spec["max_len"] == 1024
    assert set(b["assumed"]) >= {
        "in_proj_order", "gated_norm", "A_log", "dt_bias", "D", "conv",
        "time_step_limit", "norms", "initial_weights", "max_len"}
    assert b["deployment"]["chips"] == 1
    assert b["parameters"] == fl.parameters(spec) == 3_191_396_096
    assert b["parameter_bytes"] == 2 * b["parameters"]


def test_the_cell_and_what_it_reports():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "chips": 1, "traffic": MIX}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(APPENDED + NEW_METRICS)
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [(entries[n]["better"], entries[n]["layer"])
            for n in NEW_METRICS] == [
        ("higher", "model step"), ("higher", "kernels"),
        ("lower", "model step"), ("lower", "model step")]
    for name in NEW_METRICS:
        assert entries[name] == {
            "name": name, "unit": "%", "better": entries[name]["better"],
            "source": "device_trace", "layer": entries[name]["layer"],
            "moves": "serve_p95_ms", "workloads": [CELL]}
    for name in APPENDED:
        assert entries[name]["workloads"][-1] == CELL
        assert entries[name]["workloads"].count(CELL) == 1
    for name, m in entries.items():
        if name not in APPENDED + NEW_METRICS:
            assert CELL not in m["workloads"], name
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_granite" and mix["batch_size"] == 8
    assert (mix["max_wait_ms"], mix["workers"], mix["client_threads"],
            mix["warm_requests"], mix["sample_requests"],
            mix["trace_window_s"], mix["reply_timeout_s"]) == (
        5.0, 1, 96, 8, 8, 8, 60)
    assert isinstance(mix["arrivals"]["gap_seed"], int)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2", "ssm_rel_l2",
                                  "attn_rel_l2", "served_not_model",
                                  "unanswered"}
    assert set(mix["limits_why"]) == set(mix["limits"])
    assert all(len(why) > 20 for why in mix["limits_why"].values())
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    assert 0 < mix["limits"]["ssm_rel_l2"] < 0.1
    assert 0 < mix["limits"]["attn_rel_l2"] < 0.1
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


# ---- the checks of the benchmark's tests that pin it as the sixth cell left it,
# ---- as it stands now (tests/conftest.py marks the originals)

def test_the_trinity_cell_and_what_it_reports():
    """``test_trinity_cell.py::test_the_cell_and_what_it_reports`` as the
    benchmark stands, every assertion of it, changed in one place: the
    accepted readers this cell reads too end with this cell after the
    Trinity cell."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == TRINITY_CELL)
    assert cell == {**cell, "config": "trinity-mini-stage", "chips": 1,
                    "traffic": "poisson_steady_16k_trinity"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, TRINITY_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(TRINITY_APPENDED + TRINITY_METRICS)
    assert len(TRINITY_APPENDED) == 15 and set(TRINITY_APPENDED) == set(
        GENERIC + OCCUPANCY + ["moe_dispatch_share"] + SHARED_WITH_MELLUM2)
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [(entries[n]["better"], entries[n]["layer"])
            for n in TRINITY_METRICS] == [
        ("higher", "model step"), ("higher", "kernels"),
        ("lower", "model step"), ("lower", "experts")]
    for name in TRINITY_METRICS:
        assert entries[name] == {
            "name": name, "unit": "%", "better": entries[name]["better"],
            "source": "device_trace", "layer": entries[name]["layer"],
            "moves": "serve_p95_ms", "workloads": [TRINITY_CELL]}
    for name in TRINITY_APPENDED:       # the one change: this cell after
        tail = [TRINITY_CELL, CELL] if name in APPENDED else [TRINITY_CELL]
        assert entries[name]["workloads"][-len(tail):] == tail
        assert entries[name]["workloads"].count(TRINITY_CELL) == 1
    for name, m in entries.items():
        if name not in TRINITY_APPENDED + TRINITY_METRICS:
            assert TRINITY_CELL not in m["workloads"], name
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


def test_the_three_occupancy_entries():
    """``test_trinity_cell.py``'s test of the three occupancy entries
    as the benchmark stands, every assertion of it, changed in one place:
    each entry's ``workloads`` has this cell appended after Trinity's."""
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
                     "workloads": serve_cells + [TRINITY_CELL, CELL]}
    for cell in serve_cells:
        loaded = run.load_cell(ROOT, cell)
        assert [m["name"] for m in loaded["per_layer"]][-3:] == OCCUPANCY
    loaded = run.load_cell(ROOT, TRINITY_CELL)
    assert [m["name"] for m in loaded["per_layer"]][-7:] == \
        OCCUPANCY + TRINITY_METRICS
    train = run.load_cell(ROOT, "gpt2m_train")
    assert not set(OCCUPANCY) & {m["name"] for m in train["per_layer"]}
    # ... and in this cell the four new readers follow the three and
    # mellum2_flash_roofline
    loaded = run.load_cell(ROOT, CELL)
    names = [m["name"] for m in loaded["per_layer"]]
    assert names[-4:] == NEW_METRICS
    assert set(OCCUPANCY) <= set(names)


def test_the_mellum2_cell_and_what_it_reports():
    """``test_trinity_cell.py::test_the_mellum2_cell_and_what_it_reports``
    as the benchmark stands, every assertion of it, changed in one place:
    ``mellum2_flash_roofline`` and the generic readers this cell reads
    end with it after the Trinity cell."""
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
            assert m["workloads"] == (        # the one change: this cell
                [MELLUM2_CELL, TRINITY_CELL, CELL]
                if m["name"] == "mellum2_flash_roofline"
                else [MELLUM2_CELL, TRINITY_CELL]
                if m["name"] in SHARED_WITH_MELLUM2
                else [MELLUM2_CELL]) and m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC + ["moe_dispatch_share"]:  # ... and here
            tail = [MELLUM2_CELL, TRINITY_CELL] + (
                [CELL] if m["name"] in APPENDED else [])
            assert m["workloads"][-len(tail):] == tail
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
    """``test_trinity_cell.py::test_the_lfm2_cell_reports_what_it_did`` as
    the benchmark stands, every assertion of it, changed in one place:
    the generic readers this cell reads list it after the Trinity
    cell."""
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
            assert m["workloads"] == (
                [LFM2_CELL, MELLUM2_CELL, TRINITY_CELL]
                if m["name"] == "moe_dispatch_share" else [LFM2_CELL])
            assert m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC:            # the one change: this cell
            tail = [LFM2_CELL, MELLUM2_CELL, TRINITY_CELL] + (
                [CELL] if m["name"] in APPENDED else [])
            assert m["workloads"][-len(tail):] == tail
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


def test_benchmark_json_is_still_well_formed():
    """``test_trinity_cell.py::test_benchmark_json_is_still_well_formed``
    as the benchmark stands, every assertion of it, changed in one
    place: a seventh cell and configuration, and four more per-layer
    entries at the list's end."""
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", GLM_CELL,
                     LFM2_CELL, MELLUM2_CELL, TRINITY_CELL, CELL]  # change
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)
    assert [c["name"] for c in BENCH["configs"]] == [
        "gpt2-medium", "gpt2-xl", "glm-5.2-ep16", "lfm2-24b-a2b-stage",
        "mellum2-12b-a2.5b-stage", "trinity-mini-stage",
        CONFIG]                                         # ... and here
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 40
    assert [m["name"] for m in BENCH["per_layer"]][-21:] == \
        LFM2_METRICS + MELLUM2_METRICS + OCCUPANCY + TRINITY_METRICS \
        + NEW_METRICS                                   # ... and here
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"train_tokens_per_s": 0.01, "serve_p50_ms": 0.03,
                      "serve_p95_ms": 0.07, "setup_s": 0.1}
    serve = {m["name"]: m["workloads"] for m in BENCH["end_to_end"]
             if "workloads" in m}
    assert serve["serve_p50_ms"] == serve["serve_p95_ms"] == [
        "gpt2xl_serve_steady", GLM_CELL, LFM2_CELL, MELLUM2_CELL,
        TRINITY_CELL, CELL]                             # ... and here
    assert serve["train_tokens_per_s"] == ["gpt2m_train"]
    # a full check of every cell fits in 43200 s
    assert 2 + 14 * len(cells) * (BENCH["run_seconds"] + 60) \
        + 2 * 90 * len(cells) + 1200 <= 43200


# ------------------------------------------------------------- the yardstick

def test_flops_at_the_cell_s_size():
    """The configuration's arithmetic, checked against the tree."""
    spec = body()["networkSpec"]
    assert fl.mamba_sizes(spec) == {"inner": 4096, "heads": 64, "width": 64,
                                    "state": 128, "groups": 1,
                                    "channels": 4352}
    assert 2048 * 8512 == 17_432_576 and 4096 * 2048 == 8_388_608
    assert fl.mamba_params(spec) == 17_432_576 + 4 * 4352 + 4352 + 3 * 64 \
        + 4096 + 8_388_608 == 25_847_232
    assert fl.mlp_params(spec) == 50_331_648
    assert fl.layer_params(spec, "mamba") == 76_182_976
    assert fl.attention_params(spec) == 2 * 4_194_304 + 2 * 1_048_576
    assert fl.layer_params(spec, "full_attention") == 60_821_504
    assert 100352 * 2048 == 205_520_896
    assert fl.parameters(spec) == 36 * 76_182_976 + 4 * 60_821_504 \
        + 205_520_896 + 2048 == 3_191_396_096               # 6.38 GB
    parts = fl.parts_per_row(spec, 1024)
    per_row = fl.forward_flops_per_row(spec, 1024)
    assert per_row == sum(parts.values())
    assert per_row == pytest.approx(6.249e12, rel=2e-4)
    share = {k: v / per_row for k, v in parts.items()}
    assert parts["mlp"] == pytest.approx(4.123e12, rel=1e-3)
    assert share["mlp"] == pytest.approx(0.660, abs=0.001)
    assert parts["mamba_projections"] == pytest.approx(1.904e12, rel=1e-3)
    assert share["mamba_projections"] == pytest.approx(0.305, abs=0.001)
    assert parts["scan"] == pytest.approx(0.117e12, rel=5e-3)
    assert share["scan"] == pytest.approx(0.019, abs=0.001)
    assert parts["attention_projections"] == pytest.approx(0.086e12,
                                                           rel=5e-3)
    assert parts["causal_pairs"] == pytest.approx(0.017e12, rel=2e-2)
    assert parts["conv"] == pytest.approx(0.001e12, rel=0.3)
    # the scan's needed work a token and layer: 3.18 MFLOP over four
    # whole chunks (lower triangles of 256: (T + 1) / 2 pairs a token),
    # and the carry of a state a chunk; 17,152 bytes
    one = fl.scan_cost(spec, 1, 1024)
    assert fl.chunk_pairs(spec, 1024) == 4 * 256 * 257 // 2
    assert (one["flops"] - 4 * 2 * 64 * 64 * 128) / 1024 == 3_182_720
    assert one["bytes"] / 1024 == 17_152
    # a full bucket at the peak, and the scan's least time (bound by
    # bandwidth: 20.9 ns a token and layer, 6.2 ms a bucket)
    assert 8 * per_row / 197e12 == pytest.approx(0.2538, abs=1e-3)
    bucket = fl.scan_cost(spec, 8, 1024)
    assert bucket["bytes"] / 819e9 > bucket["flops"] / 197e12
    assert 36 * bucket["bytes"] / 819e9 == pytest.approx(6.17e-3, rel=1e-2)
    # a padded last chunk counts its real positions alone
    assert fl.chunk_pairs(spec, 1000) == 3 * 256 * 257 // 2 + 232 * 233 // 2
    assert fl.chunk_pairs({"mamba_chunk_size": 8}, 37) == 4 * 36 + 15
    # the older keys' counts: per-head norms on, no Mamba-2 layer
    assert fl.attention_params({**spec, "attention_qk_norm": True}) == \
        fl.attention_params(spec) + 128


# --------------------------------------------- the readers on a written profile

_J = "jit(tpu_model_forward)/HybridMoELM/"
GQA = m2.GQA
at = m2.at
# an execution: the scan's fusions under ssm_scan (36 layers: three a
# layer in ONE op name each, counted 36 times), the conv and the gated
# norm, the projections under ssm_mixer alone, the attention call, an
# MLP product and a copy outside every scope
OPS = {
    "%fusion.70 = f32[8,4,64,256,256] fusion(%a, %b)": (
        _J + "layer_0_mamba/ssm_mixer/ssm_scan/exp:",
        at(10.0, 0.2, 36, 0.1)),
    "%fusion.71 = f32[8,1024,4352] fusion(%x, %w)": (
        _J + "layer_0_mamba/ssm_mixer/ssm_conv/add:",
        at(18.0, 0.1, 36, 0.05)),
    "%fusion.72 = bf16[8,1024,4096] fusion(%y, %z)": (
        _J + "layer_0_mamba/ssm_mixer/ssm_gated_norm/mul:",
        at(22.0, 0.1, 36, 0.04)),
    "%fusion.73 = f32[8,1024,8512] fusion(%u, %w)": (
        _J + "layer_0_mamba/ssm_mixer/ble,de->ble/dot_general:",
        at(26.0, 0.1, 36, 0.08)),
    GQA: (_J + "layer_5_attn/gqa_attend/jit(_flash_forward)/pallas_call:",
          at(30.0, 0.5, 4, 0.3)),
    "%fusion.9 = bf16[8192,8192] fusion(%u, %g)": (
        _J + "layer_0_mlp/tk,kn->tn/dot_general:", at(32.0, 1.0, 1, 2.0)),
    "%copy.3 = f32[8] copy(%e)": (None, at(34.5, 1.0, 1, 1.0)),
}
MAIN_RUNS = [(10.0, 26.0), (40.0, 26.0)]
BUSY_MS = 2 * (36 * (0.1 + 0.05 + 0.04 + 0.08) + 4 * 0.3 + 2.0 + 1.0)


def context(tmp_path, ops=None, runs=None):
    xplane_scopes.device_metadata.cache_clear()
    trace_dir = m2.write_profile(tmp_path / ".bench_trace" / CELL,
                                 OPS if ops is None else ops,
                                 MAIN_RUNS if runs is None else runs)
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    reduced = trace_reduce.reduce_trace(trace_dir)
    return {"cell": cell, "trace": reduced, "peak": PEAK,
            "counters": {"rows_ok": 30, "seq": 1024, "bucket": 8,
                         "batch_rows": 5.5, "ssm_layers": 36,
                         "ssm_chunks": 4, "ssm_state_bytes": 77_377_536}}


def least(cost):
    return max(cost["flops"] / 197e12, cost["bytes"] / 819e9)


def test_new_readers_by_hand(tmp_path):
    ctx = context(tmp_path)
    spec = ctx["cell"]["config_file"]["networkSpec"]
    t = ctx["trace"]
    busy = BUSY_MS / 1e3
    assert t["module_runs"] == 2 and t["busy_s"] == pytest.approx(busy)
    need = fl.forward_flops_per_row(spec, 1024) * 30
    assert reader("granite_forward_mfu").read(ctx) == pytest.approx(
        100 * need / (busy * 197e12))
    # the scan: 36 layers' least time at 5.5 rows a bucket, two
    # executions, over 72 ops of 0.1 ms under ssm_scan
    assert reader("ssm_scan_roofline").read(ctx) == pytest.approx(
        100 * least(fl.scan_cost(spec, 5.5, 1024)) * 36 * 2 / 7.2e-3)
    assert reader("ssm_scan_share").read(ctx) == pytest.approx(
        100 * 2 * 36 * 0.1e-3 / busy)
    assert reader("ssm_conv_norm_share").read(ctx) == pytest.approx(
        100 * 2 * 36 * (0.05 + 0.04) * 1e-3 / busy)
    # ... and the accepted reader reads the cell as it is: one causal
    # call a layer of four and execution, heads of 64 over 1024 tokens
    assert reader("mellum2_flash_roofline").read(ctx) == pytest.approx(
        100 * 2 * 4 * least(fl.flash_cost(spec, "full_attention", 8, 1024))
        / 2.4e-3)


def test_no_new_reader_reads_over_a_hundred(tmp_path):
    """A full bucket at the chip's peak reads 100 at most: the needed
    work of real rows over a trace in which the step runs at its
    roofline, and the scan at its own."""
    spec = body()["networkSpec"]
    step_ms = 1e3 * 8 * fl.forward_flops_per_row(spec, 1024) / 197e12
    scan_ms = 1e3 * least(fl.scan_cost(spec, 8, 1024))
    ops = {"%fusion.70 = f32[8,4,64,256,256] fusion(%a, %b)": (
               OPS["%fusion.70 = f32[8,4,64,256,256] fusion(%a, %b)"][0],
               at(0.0, scan_ms, 36, scan_ms, (0.0,))),
           "%fusion.71 = f32[8,1024,4352] fusion(%x, %w)": (
               OPS["%fusion.71 = f32[8,1024,4352] fusion(%x, %w)"][0],
               [(36 * scan_ms, 0.5)]),
           "%fusion.72 = bf16[8,1024,4096] fusion(%y, %z)": (
               OPS["%fusion.72 = bf16[8,1024,4096] fusion(%y, %z)"][0],
               [(36 * scan_ms + 0.5, 0.5)]),
           "%fusion.1 = f32[8] fusion(%p)": (
               _J + "layer_0_mlp/tk,kn->tn/dot_general:",
               [(36 * scan_ms + 1.0, step_ms - 36 * scan_ms - 1.0)])}
    ctx = context(tmp_path, ops, [(0.0, step_ms)])
    ctx["counters"].update(rows_ok=8, batch_rows=8.0)
    assert ctx["trace"]["module_runs"] == 1
    assert ctx["trace"]["busy_s"] == pytest.approx(step_ms / 1e3, rel=1e-6)
    for name in NEW_METRICS[:2]:        # (the profile's clock is in ns)
        assert reader(name).read(ctx) == pytest.approx(100.0, rel=1e-5), name
    for name in NEW_METRICS[2:]:
        assert 0 < reader(name).read(ctx) < 100, name


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
                            "host_spans_test_for_granite")
    host_spans_test.write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    ctx = {"cell": cell, "trace": reduced, "peak": PEAK,
           "counters": {"seq": 1024, "bucket": 8, "rows_ok": 5,
                        "batch_rows": 5.5}}
    assert read(ctx) is None
    for other, tag in (("test_glm_dsa_cell.py", "glm"),
                       ("test_lfm2_cell.py", "lfm2"),
                       ("test_mellum2_cell.py", "mellum2")):
        module = _load(os.path.join(HERE, other), f"{tag}_profile_for_gr")
        xplane_scopes.device_metadata.cache_clear()
        shutil.rmtree(tmp_path / ".bench_trace")
        module.write_profile(tmp_path / ".bench_trace" / CELL)
        ctx["trace"] = trace_reduce.reduce_trace(
            str(tmp_path / ".bench_trace" / CELL))
        assert read(ctx) is None, other
    # the scopes without the program's counter: the two shares read,
    # the two that need the counter do not
    shutil.rmtree(tmp_path / ".bench_trace")
    full = context(tmp_path)
    full["counters"].pop("ssm_layers")
    got = reader(name).read(full)
    assert (got is None) == (name in NEW_METRICS[:2])


# ----------------------------------------------------------- the pinned names

def test_the_program_names_every_scope_the_readers_read():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = {**body()["networkSpec"], **TINY}
    module = build_network({"dtype": "bfloat16", **spec})
    tokens = jnp.zeros((2, 37), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    text = jax.jit(lambda p, t: module.apply({"params": p}, t)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    # the three parts lie inside the mixer, each layer's, and none in
    # another
    for i in (0, 1, 3):
        for part in ("ssm_conv", "ssm_scan", "ssm_gated_norm"):
            assert re.search(rf"layer_{i}_mamba/ssm_mixer/{part}/", text)
    for a, b in (("ssm_conv", "ssm_scan"), ("ssm_scan", "ssm_gated_norm"),
                 ("ssm_conv", "ssm_gated_norm")):
        assert not re.search(rf"{a}/([a-z_]+/)*{b}", text), (a, b)
        assert not re.search(rf"{b}/([a-z_]+/)*{a}", text), (a, b)
    assert re.search(r"layer_2_attn/gqa_attend/", text)
    assert not re.search(r"layer_2_attn/swa_attend/|moe_(route|experts)/", text)
    assert driver().TAILS == ("ssm_tail", "attention_tail")
    assert set(driver().TAILS) <= set(module.row_outputs)
    full = build_network({"dtype": "bfloat16", **body()["networkSpec"]})
    assert (full.ssm_layers, full.ssm_chunks, full.ssm_state_bytes,
            full.rope_free_layers) == (36, 4, 77_377_536, 4)


def test_reference_and_yardstick_import_nothing_of_the_program():
    for name in ("reference_granite.py", "flops_granite.py",
                 "control_granite.py"):
        text = open(os.path.join(BENCH_DIR, name)).read()
        assert not re.search(r"^\s*(from|import) mmlspark_tpu", text,
                             re.M), name
    for name in NEW_METRICS:
        text = open(os.path.join(BENCH_DIR, "metrics", name + ".py")).read()
        assert "mmlspark_tpu" not in text and "min(" not in text, name
    text = open(os.path.join(BENCH_DIR, "reference_granite.py")).read()
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "ssd" not in text.split('"""', 2)[2]
    assert "lax.scan" in text and "chunk" in text
    assert "float32" in text and "bfloat16" not in text.split('"""', 2)[2]


# ------------------------------------------------- the cell, at a tiny size

TOY_LIMITS = {"class_gap": 0.3, "logit_rel_l2": 0.02, "ssm_rel_l2": 0.02,
              "attn_rel_l2": 0.02}


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
    mix.update(client_threads=16)
    # bfloat16 against float32 at 64 wide: a product's rounding is
    # larger than the cell's, so the toy has limits of its own
    mix["limits"].update(limits or TOY_LIMITS)
    json.dump(mix, open(path, "w"))
    json.dump(BENCH, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("granite"))


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
                                     "ssm_rel_l2", "attn_rel_l2",
                                     "served_not_model", "unanswered"}
    for name in ("logit_rel_l2", "ssm_rel_l2", "attn_rel_l2"):
        assert 0 < line["compared"][name]["value"] < 0.02, name
    assert line["compared"]["served_not_model"]["value"] == 0
    info = line["info"]
    assert len(info["rows_rel_l2"]) == 8
    assert info["recompiles"] == 0 and info["sampled"] == 8
    assert (info["ssm_layers"], info["ssm_chunks"]) == (3, 5)
    assert info["ssm_state_bytes"] == 30336
    assert info["weights_cast_leaves"] == 0


def test_the_comparison_reads_the_first_layer_of_each_kind():
    import numpy as np
    drv = driver()
    spec = {**body()["networkSpec"], **TINY}
    assert drv.held_layers(spec) == {"ssm_rel_l2": 0, "attn_rel_l2": 2}
    assert drv.held_layers(body()["networkSpec"]) == {"ssm_rel_l2": 0,
                                                      "attn_rel_l2": 5}
    rng = np.random.default_rng(3)
    ref = {"logits": rng.normal(size=(2, 16)),
           "operators": {0: rng.normal(size=(2, 4, 8)),
                         2: rng.normal(size=(2, 4, 8))}}
    tails = {"ssm_tail": np.stack([ref["operators"][0],
                                   rng.normal(size=(2, 4, 8))], 1),
             "attention_tail": ref["operators"][2][:, None] * 1.01}
    tr = {"limits": {**TOY_LIMITS, "served_not_model": 0, "unanswered": 0}}
    checks = {c["name"]: c["value"] for c in drv.compare(
        ref["logits"].argmax(-1), ref["logits"], tails, ref, spec, tr, 0)}
    assert checks["ssm_rel_l2"] == 0.0              # the first entry only
    assert checks["attn_rel_l2"] == pytest.approx(0.01)
    assert checks["logit_rel_l2"] == 0.0 == checks["class_gap"]


def test_the_driver_draws_the_weights_without_the_forward():
    """The cell's ``init`` draws what ``jax.jit(module.init)`` draws,
    and its compiled program holds no product: the forward is gone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": "bfloat16", **body()["networkSpec"],
                            **TINY})
    args = (jax.random.PRNGKey(2 ** 31 + 7), jnp.zeros((1, 37), jnp.int32))
    lean, full = driver().init_lean(module), jax.jit(module.init)
    got, want = lean(*args), full(*args)
    assert sorted(got) == ["params"] and sorted(want) == ["params", "stats"]
    assert jax.tree_util.tree_structure(got["params"]) \
        == jax.tree_util.tree_structure(want["params"])
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(got["params"]),
        jax.tree_util.tree_leaves(want["params"])))
    assert " dot(" in full.lower(*args).compile().as_text()
    assert " dot(" not in lean.lower(*args).compile().as_text()


def test_controls_read_not_correct(root):
    """The program reads correct and the reference with one thing
    changed (``control_granite.STAND_INS``) in its place does not, each
    by the check that is its own."""
    import control_granite
    assert set(control_granite.STAND_INS) == {
        "fp8", "no_carry", "no_decay", "no_dt_bias", "no_d_skip",
        "norm_after_gate", "conv_reversed", "no_conv_bias", "bc_swapped",
        "no_residual_multiplier", "no_embedding_multiplier",
        "no_logits_scaling", "sqrt_scale", "qk_norm", "rope", "kv_mod"}
    drv = driver()
    cell = run.load_cell(root, CELL)
    cell["seconds"] = 1.0
    got = drv.control(cell, 17, ["sound", *control_granite.STAND_INS])
    info = got.pop("info")
    value = {name: {c["name"]: c["value"] for c in checks}
             for name, checks in got.items()}
    assert run.judge(got["sound"]), value["sound"]
    assert len(info["rows_rel_l2_sound"]) == 8
    for name in control_granite.STAND_INS:
        assert not run.judge(got[name]), (name, value[name])
        assert len(info[f"rows_rel_l2_{name}"]) == drv.CONTROL_ROWS == 2
    sound = value["sound"]
    # the Mamba-2 arithmetic by layer 0's operator ...
    for name in ("no_carry", "no_decay", "no_dt_bias", "no_d_skip",
                 "norm_after_gate", "conv_reversed", "no_conv_bias",
                 "bc_swapped", "fp8"):
        assert value[name]["ssm_rel_l2"] > 0.02 \
            > 2 * sound["ssm_rel_l2"], (name, value[name])
    # ... which attention's stand-ins and the head's scale do not move
    for name in ("sqrt_scale", "qk_norm", "rope", "kv_mod",
                 "no_logits_scaling", "no_residual_multiplier"):
        assert value[name]["ssm_rel_l2"] < 1e-5, (name, value[name])
    # the norm before the operator takes a scale out of layer 0
    assert value["no_embedding_multiplier"]["ssm_rel_l2"] < 0.01
    # attention's scale, norms, table and grouping by layer 2's output
    for name in ("sqrt_scale", "qk_norm", "rope", "kv_mod"):
        assert value[name]["attn_rel_l2"] > 0.02 \
            > 2 * sound["attn_rel_l2"], (name, value[name])
    # the multipliers by the logits
    for name in ("no_residual_multiplier", "no_embedding_multiplier",
                 "no_logits_scaling"):
        assert value[name]["logit_rel_l2"] > 0.1 \
            > 5 * sound["logit_rel_l2"], (name, value[name])
