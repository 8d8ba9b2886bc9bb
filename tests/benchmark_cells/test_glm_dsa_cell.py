"""The ``glm52_score_8k_steady`` cell's own tests: CPU only, a tiny
preset. The configuration's entry and file (with its cuts), the
yardstick ``flops_glm_dsa`` against hand counts, the scopes read from a
profile written by hand, each new reader by hand and silent with
nothing to read, the names the readers find things by, the driver end
to end and the controls of ``correct``.

No topology or TPU call is made anywhere in this file: the kernel's
name is read from a lowering for the TPU, which needs no TPU library.
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
CELL = "glm52_score_8k_steady"
CONFIG = "glm-5.2-ep16"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_glm_dsa")
import flops_glm_dsa as fl   # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce          # noqa: E402
import xplane_scopes         # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_METRICS = ["glm_forward_mfu", "dsa_attend_roofline", "dsa_select_share",
               "moe_experts_roofline", "moe_load_max_over_mean"]
SERVE_READERS = ["serve_queue_wait_ms", "scorer_device_wait_ms",
                 "device_idle_serve", "serve_token_wait_ms",
                 "serve_dispatch_wait_ms", "serve_worker_host_ms",
                 "device_idle_serve_named"]
SCOPES = ("mla_project", "dsa_score", "dsa_topk", "dsa_attend", "moe_route",
          "moe_experts", "moe_shared", "lm_head_last")
TINY = {"vocab_size": 128, "max_len": 32, "hidden_size": 64,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
        "rope_theta": 10000.0, "index_n_heads": 4, "index_head_dim": 16,
        "index_topk": 8, "intermediate_size": 128,
        "moe_intermediate_size": 32, "experts_total": 16,
        "experts_held": 4, "expert_rank": 1, "num_experts_per_tok": 4}


def body():
    return json.load(open(os.path.join(BENCH_DIR, "configs",
                                       CONFIG + ".json")))


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


# ------------------------------------------------- BENCHMARK.json and the file

def test_config_entry_and_its_file_with_cuts():
    """``test_config_entry_and_its_file`` with ``reduced`` as it stands
    (see conftest.py), and what a cut configuration's file has to say."""
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG for w in BENCH["workloads"])
    b = body()
    assert b["source"] == config["source"]
    assert b["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    # the published values of what was cut stand beside the cut ones
    assert b["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    assert (b["num_hidden_layers"], b["first_k_dense_replace"],
            b["n_routed_experts"], b["vocab_size"],
            b["num_nextn_predict_layers"]) == (5, 1, 16, 19360, 0)
    # no width is cut: the file's published keys and what is run agree
    spec = b["networkSpec"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "index_n_heads", "index_head_dim",
                "index_topk", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "rms_norm_eps"):
        assert spec[key] == b[key], key
    assert (b["hidden_size"], b["moe_intermediate_size"], b["index_topk"],
            b["qk_head_dim"], b["v_head_dim"]) == (6144, 2048, 2048, 256, 256)
    assert spec["rope_theta"] == b["rope_parameters"]["rope_theta"] == 8e6
    assert (spec["experts_held"], spec["experts_total"],
            spec["expert_rank"]) == (16, 256, 0)
    assert b["vocab_size"] == spec["vocab_size"] >= 154880 // 8
    # one whole period of the published patterns, from layer 2 on
    assert spec["indexer_types"] == b["indexer_types"][2:7] == [
        "full", "shared", "shared", "shared", "full"]
    assert spec["mlp_layer_types"] == b["mlp_layer_types"][2:7]
    assert len(b["indexer_types"]) == len(b["mlp_layer_types"]) == 78
    assert b["deployment"]["chips_sharing_a_layer"] == 16
    assert any("MTP" in d and "not run" in d for d in b["departures"])
    assert b["parameters"] == fl.parameters(spec) == 3_881_517_056
    assert b["parameter_bytes"] == 2 * b["parameters"] + 2 * 4 * 256
    assert "bfloat16" in b["precision"]


def test_the_cell_and_what_it_reports():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "chips": 1,
                    "traffic": "poisson_steady_8k"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == \
        SERVE_READERS + NEW_METRICS
    # the GPT-2 step's readers stay GPT-2's
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline"):
            assert CELL not in m["workloads"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"
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
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", CELL]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)


# ------------------------------------------------------- the yardstick by hand

def test_flops_by_hand_for_one_tiny_shape():
    s = {**TINY, "n_shared_experts": 1,
         "indexer_types": ["full", "shared"],
         "mlp_layer_types": ["dense", "sparse"]}
    length = 32
    # queries 0-7 keep 1..8 keys, the other 24 keep 8
    assert fl.selected_pairs(length, 8) == 36 + 24 * 8 == 228
    # ... and only those 24 need a score: 32*33/2 - 8*9/2 causal pairs
    assert fl.scored_pairs(length, 8) == 528 - 36 == 492
    assert fl.scored_pairs(8, 8) == 0 == fl.selector_flops(s, 8)
    proj = 2 * length * (64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 40
                         + 4 * 24 * 64)
    assert fl.attention_projection_flops(s, length) == proj
    attend = 2 * 228 * 4 * (24 + 24)
    assert fl.attend_cost(s, length) == {
        "flops": attend,
        "bytes": 4 * length * (2 * 24 + 2 * 24) * 2 + length * length}
    selector = 2 * length * (32 * 4 * 16 + 64 * (16 + 4)) \
        + 2 * 492 * 4 * 16
    assert fl.selector_flops(s, length) == selector
    dense = 2 * 3 * 64 * 128 * length
    router = 2 * length * 64 * 16
    shared = 2 * 3 * 64 * 32 * length
    pairs = length * 4 * 4 / 16                  # uniform routing: 32
    assert fl.expected_pairs_held(s, length) == pairs
    routed = 2 * 3 * 64 * 32 * pairs
    head = 2 * 64 * 128
    want = 2 * (proj + attend) + selector + dense + router + shared \
        + routed + head
    assert fl.forward_flops_per_row(s, length) == want
    # the program's own count of the pairs routed here takes the
    # expectation's place
    assert fl.forward_flops_per_row(s, length, 40.0) == \
        want - routed + 2 * 3 * 64 * 32 * 40.0
    assert fl.experts_cost(s, 40.0) == {
        "flops": 2 * 3 * 64 * 32 * 40.0,
        "bytes": (4 * 3 * 64 * 32 + 40.0 * 2 * 64) * 2}


def test_flops_at_the_cell_s_size():
    spec = body()["networkSpec"]
    # ISSUE 30's count: 14.7 M of a row's 33.6 M causal pairs are kept
    assert fl.selected_pairs(8192, 2048) == 14_681_088
    assert 8192 * 8193 // 2 == 33_558_528
    per_row = fl.forward_flops_per_row(spec, 8192)
    assert 26.5e12 < per_row < 27e12
    assert 0.95e12 < fl.attend_cost(spec, 8192)["flops"] < 0.97e12
    assert fl.expected_pairs_held(spec, 8192) == 4096


# --------------------------------------------- scopes of a hand-written profile

OPS = {  # name -> (scope or None, [(start ms, length ms)])
    "%fusion.1 = f32[8] fusion(%p)": (
        "jit(tpu_model_forward)/LatentMoELM/layer_0_attn/while/body/"
        "closed_call/dsa_score/njd,md->njm/dot_general:", [(10.0, 1.0),
                                                           (40.0, 1.0)]),
    "%fusion.2 = pred[8] fusion(%q)": (
        "jit(tpu_model_forward)/LatentMoELM/layer_0_attn/while/body/"
        "closed_call/dsa_topk/while/body/reduce_sum:", [(11.0, 0.5),
                                                        (41.0, 0.5)]),
    "%dsa_attend.32 = bf16[1,64,8192,256] custom-call(%a, %b), "
    "custom_call_target=\\\"tpu_custom_call\\\"": (
        "jit(tpu_model_forward)/LatentMoELM/layer_0_attn/while/body/"
        "closed_call/dsa_attend/jit(_dsa_attend)/dsa_attend/pallas_call:",
        [(12.0, 4.0), (16.0, 4.0), (42.0, 4.0), (46.0, 4.0)]),
    "%gmm.49 = f32[20480,2048] custom-call(%c), "
    "custom_call_target=\\\"tpu_custom_call\\\"": (
        "jit(tpu_model_forward)/LatentMoELM/layer_1_moe/moe_experts/while/"
        "body/jit(_moe_grouped_matmul)/jit(gmm)/pallas_call:",
        [(20.0, 2.0), (50.0, 2.0)]),
    "%fusion.9 = f32[20480,6144] fusion(%d)": (
        "jit(tpu_model_forward)/LatentMoELM/layer_1_moe/moe_experts/while/"
        "body/scatter-add:", [(22.0, 3.0), (52.0, 3.0)]),
    "%copy.3 = f32[8] copy(%e)": (None, [(25.0, 5.0), (55.0, 5.0)]),
}
MAIN_RUNS = [(10.0, 20.0), (40.0, 20.0)]


def write_profile(trace_dir):
    from jax.profiler import ProfileData

    def ps(ms):
        return int(round(ms * 1e9))
    meta, events = [], []
    names = ["jit_tpu_model_forward(3)"] + list(OPS)
    for i, name in enumerate(names, 1):
        scope = OPS.get(name, (None,))[0]
        stats = (f'stats {{ metadata_id: 1 str_value: "{scope}" }} '
                 f'stats {{ metadata_id: 2 int64_value: 7 }}'
                 if scope else "stats { metadata_id: 2 int64_value: 7 }")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}" {stats} }} }}')
        for start, length in OPS.get(name, (None, []))[1]:
            events.append(f"events {{ metadata_id: {i} offset_ps: "
                          f"{ps(start)} duration_ps: {ps(length)} }}")
    mods = " ".join(f"events {{ metadata_id: 1 offset_ps: {ps(s)} "
                    f"duration_ps: {ps(n)} }}" for s, n in MAIN_RUNS)
    text = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" {mods} }}
  lines {{ id: 2 name: "XLA Ops" {" ".join(events)} }}
  {" ".join(meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "program_id" }} }} }}
planes {{ id: 2 name: "/host:CPU" }}
'''
    d = os.path.join(str(trace_dir), "plugins", "profile", "t0")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(trace_dir)


@pytest.fixture()
def traced(tmp_path):
    """(context as run.py builds it for the readers, checkout root)."""
    xplane_scopes.device_metadata.cache_clear()
    trace_dir = write_profile(tmp_path / ".bench_trace" / CELL)
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    reduced = trace_reduce.reduce_trace(trace_dir)
    return {"cell": cell, "trace": reduced,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {"rows_ok": 7, "seq": 8192, "bucket": 4,
                         "batch_rows": 3.5, "moe_tokens_held": 16000.0,
                         "moe_load_max_over_mean": 1.8,
                         "dsa_keys_per_query": 1792.125}}


def test_scopes_are_read_from_the_profile_s_metadata(traced):
    scope_of = xplane_scopes.for_run(traced)
    assert set(scope_of) == {n.replace('\\"', '"') for n, (s, _)
                             in OPS.items() if s}
    assert all(v.startswith("jit(tpu_model_forward)/")
               for v in scope_of.values())
    t = traced["trace"]
    assert t["module_runs"] == 2 and t["busy_s"] == pytest.approx(0.039)
    assert xplane_scopes.seconds_under(t, scope_of, "dsa_score") == (
        pytest.approx(0.002), 2)
    assert xplane_scopes.seconds_under(t, scope_of, "dsa_topk") == (
        pytest.approx(0.001), 2)
    assert xplane_scopes.seconds_under(t, scope_of, "moe_experts") == (
        pytest.approx(0.010), 4)
    # a step of the path, never a part of a step's name
    assert xplane_scopes.seconds_under(t, scope_of, "dsa") == (0.0, 0)
    # other stats of the metadata are there under their own names
    meta = xplane_scopes.device_metadata(os.path.join(
        traced["cell"]["root"], ".bench_trace", CELL))
    assert all(m["stats"]["program_id"] == 7 for m in meta.values())


def test_new_readers_by_hand(traced):
    spec = traced["cell"]["config_file"]["networkSpec"]
    need = fl.forward_flops_per_row(spec, 8192, 16000.0) * 7
    # busy: 19.5 of each execution's 20 ms (a gap after dsa_topk)
    assert reader("glm_forward_mfu").read(traced) == pytest.approx(
        100 * need / (0.039 * 197e12))
    # four calls of 4 ms: one a layer, row of the bucket and execution
    # would be 2 x 5 x 4; this trace holds 4, so the reader is silent
    assert reader("dsa_attend_roofline").read(traced) is None
    one_layer = json.loads(json.dumps(traced))
    one_layer["cell"]["config_file"]["networkSpec"]["indexer_types"] = \
        ["full"]
    one_layer["counters"]["bucket"] = 2
    least = fl.attend_cost(spec, 8192)["flops"] / 197e12
    assert reader("dsa_attend_roofline").read(one_layer) == pytest.approx(
        100 * 4 * least / 0.016)
    assert reader("dsa_select_share").read(traced) == pytest.approx(
        100 * 0.003 / 0.039)
    # the grouped product: the custom calls under moe_experts (4 ms of
    # the scope's 10), 4 expert layers an execution, 2 executions
    pairs = 16000.0 / 4 * 3.5
    cost = fl.experts_cost(spec, pairs)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert reader("moe_experts_roofline").read(traced) == pytest.approx(
        100 * least * 4 * 2 / 0.004)
    assert reader("moe_load_max_over_mean").read(traced) == 1.8


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    """The parent's program, an untraced context, a profile without the
    scopes or the kernel: None, and nothing raised."""
    xplane_scopes.device_metadata.cache_clear()
    read = reader(name).read
    cell = run.load_cell(ROOT, CELL)
    cell["root"] = str(tmp_path)
    assert read({"cell": cell, "trace": None, "peak": None,
                 "counters": {}}) is None
    # a profile of a program that has none of this: GPT-2's
    host_spans_test = _load(os.path.join(
        ROOT, "tests", "benchmark_cells", "test_host_spans.py"),
        "host_spans_test_for_glm")
    host_spans_test.write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL))
    ctx = {"cell": cell, "trace": reduced,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "counters": {"seq": 8192, "bucket": 4}}
    assert read(ctx) is None


# ----------------------------------------------------------- the pinned names

def test_kernel_and_reader_agree_on_the_custom_call_s_name():
    """The reader's pattern matches the custom call as a v5e trace names
    it (``%dsa_attend.32 = ... custom-call(``, my chip run, PR 30), and
    the kernel still lowers under that name for the TPU."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.selected_attention import _dsa_attend
    pattern = re.compile(reader("dsa_attend_roofline").DSA_ATTEND)
    assert pattern.search(
        '%dsa_attend.32 = bf16[1,64,8192,256]{3,2,1,0:T(8,128)(2,1)} '
        'custom-call(%fusion.1061, %maximum_bitcast_fusion.22)')
    assert not pattern.search(
        '%fusion.1061 = bf16[1,64,8192,256] fusion(%dsa_attend.3)')
    assert not pattern.search(
        '%_flash_forward.4 = (bf16[200,1024,64]) custom-call(%bitcast.19)')
    assert not re.search(trace_reduce.FLASH_FORWARD,
                         '%dsa_attend.32 = bf16[8] custom-call(%a)')
    shape = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda q, k, v, m: _dsa_attend(q, k, v, m)).trace(
        shape((1, 4, 512, 128), jnp.bfloat16),
        shape((1, 4, 512, 128), jnp.bfloat16),
        shape((1, 4, 512, 128), jnp.bfloat16),
        shape((1, 512, 512), jnp.int8)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]+)"', text) == ["dsa_attend"]


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
    assert tuple(module.row_stats) == (
        "moe_tokens_held", "moe_load_max_over_mean", "dsa_keys_per_query")
    serve_lm = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                            "serve_lm.py"))
    assert serve_lm.ROW_STATS == tuple(module.row_stats)


def test_reference_and_yardstick_import_nothing_of_the_program():
    for name in ("reference_glm_dsa.py", "flops_glm_dsa.py",
                 "xplane_scopes.py"):
        text = open(os.path.join(BENCH_DIR, name)).read()
        assert not re.search(r"^\s*(from|import) mmlspark_tpu", text,
                             re.M), name
    text = open(os.path.join(BENCH_DIR, "reference_glm_dsa.py")).read()
    assert 'default_matmul_precision("highest")' in text


# ------------------------------------------ near ties, by hand (no model)

def test_a_row_whose_last_position_is_a_near_tie_is_set_aside():
    """Row 2 chose its experts by a hair in layer 3 and the program
    went the other way: its logits are far off and say nothing. The
    same logits with a wide margin are a fault."""
    import numpy as np
    serve_lm = run.load_module(os.path.join(BENCH_DIR, "drivers",
                                            "serve_lm.py"))
    rng = np.random.default_rng(0)
    ref_logits = rng.normal(size=(4, 50)).astype(np.float32)
    model = ref_logits + 1e-3
    model[2] = rng.normal(size=50)
    served = model.argmax(-1)
    margin = {1: np.full((4, 6), 0.05), 3: np.full((4, 6), 0.05)}
    margin[3][2, -1] = 1e-4           # the last position, nearly a tie
    margin[1][0, 2] = 1e-5            # an earlier position: no matter
    table = np.tril(np.ones((4, 6, 6), bool))
    ref = {"logits": ref_logits, "router_margin": margin,
           "selected": {4: table}}
    tr = {"near_tie_margin": 1e-3,
          "limits": {"class_gap": 0.05, "logit_rel_l2": 0.03,
                     "near_tie_rows": 3, "select_miss": 0.02,
                     "served_not_model": 0, "unanswered": 0}}
    assert serve_lm.near_tie_rows(ref, 1e-3).tolist() == [
        False, False, True, False]
    assert serve_lm.last_margins(ref)[2].tolist() == [0.05, 1e-4]
    assert serve_lm.row_rel_l2(model, ref_logits).argmax() == 2

    def compared():
        checks = serve_lm.compare(served, model, table, ref, 4, tr, 0)
        return checks, {c["name"]: c["value"] for c in checks}
    checks, value = compared()
    assert [c["name"] for c in checks] == [
        "class_gap", "logit_rel_l2", "served_not_model", "unanswered",
        "near_tie_rows", "select_miss"]
    assert value["near_tie_rows"] == 1 and value["logit_rel_l2"] < 2e-3
    assert value["class_gap"] == 0 and run.judge(checks)
    margin[3][2, -1] = 0.05           # no tie: the row is the program's
    checks, value = compared()
    assert value["near_tie_rows"] == 0 and value["logit_rel_l2"] > 0.3
    assert not run.judge(checks)
    # every row a near tie: nothing decides, and that is not correct
    tr["near_tie_margin"] = 1.0
    checks, value = compared()
    assert value["near_tie_rows"] == 4 and not run.judge(checks)


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
    path = os.path.join(root, "benchmark", "traffic",
                        "poisson_steady_8k.json")
    mix = json.load(open(path))
    mix["arrivals"]["rate_per_s"] = 20.0
    mix.update(client_threads=16, reply_timeout_s=60)
    # bfloat16 against float32 at 64 wide: a flipped key or expert is
    # a tenth of a logit's size, so the toy gets room the cell has not
    mix["limits"].update(limits or {"class_gap": 0.2, "logit_rel_l2": 0.15,
                                    "select_miss": 0.1})
    json.dump(mix, open(path, "w"))
    json.dump(BENCH, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def line(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("glm"))
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
                                     "near_tie_rows", "select_miss",
                                     "served_not_model", "unanswered"}
    assert 0 <= line["compared"]["near_tie_rows"]["value"] <= 6
    assert len(line["info"]["rows_rel_l2"]) == 8 == len(
        line["info"]["rows_margin_min"])
    assert line["compared"]["served_not_model"]["value"] == 0
    assert 0 <= line["compared"]["select_miss"]["value"] < 0.1
    info = line["info"]
    assert info["recompiles"] == 0 and info["sampled"] == 8
    # the model's counters of the window: a row a request
    assert info["rows_scored"] == 30
    assert info["dsa_keys_per_query"] == pytest.approx(7.125)
    assert info["moe_load_max_over_mean"] >= 1.0
    assert 0 < info["moe_tokens_held"] < 4 * 32 * 4
