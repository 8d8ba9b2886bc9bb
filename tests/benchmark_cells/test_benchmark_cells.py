"""The benchmark's own tests (BENCHMARK.json, benchmark/): CPU only, tiny
sizes. The yardstick's arithmetic against hand counts, the trace
reduction against the committed trace, the generator's schedule, the
plain reference against ``build_network``, both drivers end to end, the
faults that ``correct`` has to catch, and the shape of BENCHMARK.json.

No topology or TPU call is made at import time.
"""

import importlib.util
import json
import math
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run")
import flops          # noqa: E402  (run.py put benchmark/ on the path)
import loadgen        # noqa: E402
import reference      # noqa: E402
import trace_reduce   # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
TINY_SPEC = {"vocab_size": 211, "dim": 32, "depth": 2, "heads": 4,
             "max_len": 64}


def spec_of(config):
    path = next(c["file"] for c in BENCH["configs"] if c["name"] == config)
    return json.load(open(os.path.join(ROOT, path)))["networkSpec"]


# ---------------------------------------------------------------- flops

@pytest.mark.parametrize("config,blocks,total", [
    # 12 d^2 a block; embedding + positions + blocks + norms + head
    ("gpt2-medium", 12 * 1024 ** 2 * 24, 406_336_593),
    ("gpt2-xl", 12 * 1600 ** 2 * 48, 1_557_636_816),
])
def test_flops_parameter_counts(config, blocks, total):
    spec = spec_of(config)
    assert flops.block_matmul_params(spec) == blocks
    assert flops.total_params(spec) == total
    stated = json.load(open(os.path.join(
        BENCH_DIR, "configs", config + ".json")))["parameters"]
    assert stated == total


def test_flops_train_token_by_hand():
    spec = spec_of("gpt2-medium")
    matmul = 301_989_888 + 1024 * 50257          # blocks + LM head
    attn = 6 * 1024 * 1024 * 24                  # 6 S d a layer
    assert flops.train_flops_per_token(spec, 1024) == 6 * matmul + attn
    assert 2.26e9 < flops.train_flops_per_token(spec, 1024) < 2.28e9


def test_flops_forward_row_by_hand():
    spec = spec_of("gpt2-xl")
    want = (2 * 12 * 1600 ** 2 * 48 * 1024        # block matmuls
            + 2 * 1024 * 1024 * 1600 * 48         # causal attention
            + 2 * 1600 * 16)                      # the pooled head
    assert flops.forward_flops_per_row(spec, 1024) == want
    assert 3.1e12 < want < 3.3e12


def test_flops_flash_costs_and_roofline():
    fwd = flops.flash_forward_cost(8, 16, 1024, 64)
    bwd = flops.flash_backward_cost(8, 16, 1024, 64)
    assert fwd["flops"] == 2 * 8 * 16 * 1024 * 1024 * 64     # causal half
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2 + 4 * 8 * 16 * 1024
    peak = flops.peaks("TPU v5 lite")
    roof = flops.roofline_seconds(fwd, peak)
    assert roof["bound"] == "compute"
    assert roof["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    thin = flops.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peak)
    assert thin["bound"] == "memory" and thin["seconds"] == 1.0


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_known_kind(kind):
    peak = flops.peaks(kind)
    assert peak["bf16_flops"] == 197e12 and peak["int8_ops"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
def test_peaks_refuses_unknown_kind(kind):
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        flops.peaks(kind)


# --------------------------------------------------------- trace_reduce

@pytest.fixture(scope="module")
def committed_trace():
    path = os.path.join(ROOT, "docs", "profiles",
                        "resnet20_train_step.xplane.pb")
    return {skip: trace_reduce.reduce_trace(path, skip) for skip in (0, 1)}


@pytest.mark.parametrize("skip", [0, 1])
def test_trace_busy_plus_idle_is_window(committed_trace, skip):
    r = committed_trace[skip]
    idle = sum(dur for _, dur in r["gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["module_runs"] == 2 - skip and r["main_module"] == "jit_f"


def test_trace_while_wrapper_is_not_counted(committed_trace):
    r = committed_trace[1]
    # one steady step: the leaves fill 98.9% of it and never exceed it,
    # which they would by a factor of two with the wrapper counted
    assert 0.98 < r["busy_s"] / r["window_s"] <= 1.0
    assert not [n for n in r["ops"] if re.search(r"\bwhile\(", n)]


def test_trace_kernel_seconds_and_breakdown(committed_trace):
    r = committed_trace[1]
    seconds, count = trace_reduce.kernel_seconds(r, r"fusion")
    assert count > 100 and 0 < seconds <= r["busy_s"]
    assert trace_reduce.kernel_seconds(r, r"no_such_kernel") == (0, 0)
    bd = trace_reduce.breakdown(r)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(" " not in name and secs > 0
               for name, secs in bd["device_ops"])
    times = [secs for _, secs in bd["device_ops"]]
    assert times == sorted(times, reverse=True)


def test_trace_leaves_drop_control_flow_and_keep_what_holds_a_marker():
    events = [(0, 100, "%while.7 = (s32[]) while(%tuple), body=%b"),
              (0, 40, "%a = f32[8] fusion(%x), kind=kLoop, calls=%f"),
              (50, 90, "%call.2 = f32[8] call(%a), to_apply=%g"),
              # a kernel that starts with a marker of no length
              (55, 60, "%_flash_forward.3 = bf16[8] custom-call(%q)"),
              (55, 55, "%custom-call.9 = bf16[8] custom-call(%s), "
                       'custom_call_target="ConcatBitcast"'),
              (120, 130, "%conditional.1 = f32[] conditional(%p)")]
    kept = [n.split(" = ")[0] for _, _, n in trace_reduce._leaves(events)]
    assert kept == ["%a", "%custom-call.9", "%_flash_forward.3"]


# -------------------------------------------------------------- loadgen

ARRIVALS = {"rate_per_s": 20.0, "gap_seed": 7}


def test_schedule_replays_and_fills_the_window():
    a = loadgen.schedule(ARRIVALS, 10.0)
    assert np.array_equal(a, loadgen.schedule(ARRIVALS, 10.0))
    assert len(a) == 200 and a[-1] == pytest.approx(10.0)
    assert np.all(np.diff(a) > 0)
    # exponential gaps: their spread is about their mean
    gaps = np.diff(np.concatenate([[0.0], a]))
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_schedule_differs_with_the_gap_seed():
    a = loadgen.schedule(ARRIVALS, 10.0)
    b = loadgen.schedule({**ARRIVALS, "gap_seed": 8}, 10.0)
    assert len(a) == len(b) and not np.array_equal(a, b)


def test_token_rows_from_seed():
    a = loadgen.token_rows(9, 5, 16, 100)
    assert a.shape == (5, 16) and a.min() >= 0 and a.max() < 100
    assert np.array_equal(a, loadgen.token_rows(9, 5, 16, 100))
    assert not np.array_equal(a, loadgen.token_rows(10, 5, 16, 100))


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 50, 50), (list(range(1, 101)), 95, 95),
    ([5.0], 95, 5.0), ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 100, 4),
])
def test_percentile_by_rank(values, q, want):
    assert loadgen.percentile(values, q) == want


def test_a_failed_request_is_slower_than_any():
    result = {"due": [0.0, 0.1, 0.2, 0.3], "done": [0.5, None, 0.4, 0.9],
              "status": [200, -1, 200, 503]}
    lat = loadgen.latencies_ms(result, failed_ms=120000.0)
    assert lat == pytest.approx([500.0, 120000.0, 200.0, 120000.0])
    assert loadgen.percentile(lat, 50) == 500.0
    assert loadgen.percentile(lat, 95) == 120000.0
    assert math.isnan(loadgen.percentile([], 50))


# ------------------------------------------------------------ reference

@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = {"type": "transformer", **TINY_SPEC, "max_len": 16}
    module = build_network(spec)
    variables = module.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 16), jnp.int32), train=False)
    # biases and norms start at 0 and 1: move them so that they matter
    variables = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape), variables)
    toks = np.random.default_rng(0).integers(0, 211, (4, 16))
    return module, variables, toks


def test_reference_forward_matches_the_network(tiny_lm):
    import jax
    import jax.numpy as jnp
    module, variables, toks = tiny_lm
    with jax.default_matmul_precision("highest"):
        want = np.asarray(module.apply(variables, jnp.asarray(toks)))
    got = reference.forward(variables["params"], toks, 4)
    assert got.shape == want.shape == (4, 16, 211)
    assert np.abs(got - want).max() < 2e-5
    # the control is the same mathematics in 8 bits: near, not equal
    for low in ("int8", "fp8"):
        ctl = reference.forward(variables["params"], toks, 4, low)
        assert 1e-3 < np.abs(ctl - want).max() < 1.0


def test_reference_classifier_head_reads_the_mean_token():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = {"type": "transformer", **TINY_SPEC, "max_len": 16,
            "num_classes": 5}
    module = build_network(spec)
    variables = module.init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 16), jnp.int32))
    toks = np.random.default_rng(1).integers(0, 211, (3, 16))
    want = np.asarray(module.apply(variables, jnp.asarray(toks)))
    got = reference.forward(variables["params"], toks, 4,
                            rows_per_block=2)
    assert got.shape == (3, 5) and np.abs(got - want).max() < 2e-5


@pytest.fixture(scope="module")
def tiny_training(tiny_lm):
    import jax
    import jax.numpy as jnp
    import optax
    module, variables, toks = tiny_lm
    tgts = np.roll(toks, -1, 1)
    batches = [(toks, tgts), (toks[::-1], tgts[::-1]), (toks[:2], tgts[:2])]
    tx = optax.adamw(1e-3, weight_decay=0.1)
    params = variables["params"]
    state = tx.init(params)

    def loss_fn(p, t, y):
        logits = module.apply({"params": p}, t).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean(-1).mean()
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t, y in batches:
            loss, g = jax.value_and_grad(loss_fn)(
                params, jnp.asarray(t), jnp.asarray(y))
            first = g if first is None else first
            losses.append(float(loss))
            upd, state = tx.update(g, state, params)
            params = optax.apply_updates(params, upd)
    return variables["params"], batches, losses, first, params


def test_reference_training_matches_optax_adamw(tiny_training):
    params, batches, losses, first, after = tiny_training
    got = reference.train_follow(params, batches, 4, 1e-3, 0.1)
    assert got["losses"] == pytest.approx(losses, abs=2e-5)
    want = reference.change_norms(after, params, got["moved"])
    assert "block_1/qkv/kernel" in want and len(want) == 2 * 12 + 6
    assert got["change_norms"] == pytest.approx(want, rel=1e-3)


def test_reference_leaves_out_what_only_round_off_moves(tiny_training):
    import jax
    params, batches, _, first, _ = tiny_training
    moved = reference.train_follow(params, batches[:1], 4, 1e-3, 0.1)[
        "moved"]
    # the key third of the fused qkv bias has no gradient under softmax;
    # a token the first batch does not hold has none in the embedding
    bias = np.asarray(moved["block_0"]["qkv"]["bias"])
    assert bias[:32].all() and not bias[32:64].any() and bias[64:].all()
    rows = np.asarray(moved["embed"]["embedding"]).any(axis=1)
    assert set(np.flatnonzero(rows)) == set(batches[0][0].ravel())
    kept = np.mean([np.asarray(m).mean()
                    for m in jax.tree_util.tree_leaves(moved)])
    assert kept > 0.9


@pytest.mark.parametrize("fault,step", [("state_unchanged", 1),
                                        ("half_batch", 0)])
def test_reference_planted_faults_move_the_losses(tiny_training, fault,
                                                  step):
    params, batches, losses, _, _ = tiny_training
    got = reference.train_follow(params, batches, 4, 1e-3, 0.1,
                                 fault=fault)
    assert abs(got["losses"][step] - losses[step]) > 0.02
    if fault == "state_unchanged":
        assert set(got["change_norms"].values()) == {0.0}


# ---------------------------------------------- the cells, at tiny sizes

def make_root(tmp_path, extra=None):
    """A checkout of the benchmark alone, with every configuration cut
    to a toy and every mix to a second of work."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    for cfg in bench["configs"]:
        path = os.path.join(root, cfg["file"])
        body = json.load(open(path))
        body["networkSpec"].update(TINY_SPEC)
        json.dump(body, open(path, "w"))
    for name in os.listdir(os.path.join(root, "benchmark", "traffic")):
        path = os.path.join(root, "benchmark", "traffic", name)
        mix = json.load(open(path))
        if mix["driver"] == "train":
            mix.update(rows=32, nominal_steps_per_s=4.0)
            # leaves of a few hundred elements read noisier than the
            # cell's: the toy gets ten times the room
            mix["limits"]["change_median_gap"] *= 10
        else:
            mix["arrivals"]["rate_per_s"] = 40.0
            mix.update(sample_requests=8, client_threads=16)
        json.dump(mix, open(path, "w"))
    if extra:
        extra(root, bench)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def check(line, name):
    return line["compared"][name]["value"]


@pytest.fixture(scope="module")
def train_line(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("train"))
    return run.run_cell(root, "gpt2m_train", 2 ** 31 + 5, 1.0, False,
                        require_tpu=False)


def test_train_cell_end_to_end(train_line):
    line = train_line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 4 * line["info"]["epochs"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"loss2_gap", "loss_mean_gap",
                                     "replay_gap", "change_gap",
                                     "change_median_gap"}
    # float32 against bfloat16 at a toy size: well inside the limits
    assert all(c["value"] <= c["limit"] / 2
               for c in line["compared"].values())
    assert check(line, "replay_gap") == 0
    assert 0 < check(line, "change_gap")
    assert line["info"]["change_gap_leaf"].count("/") >= 1


def _break_learner(monkeypatch, fault):
    from mmlspark_tpu.models import learner
    if fault == "state_unchanged":
        monkeypatch.setattr(learner.optax, "apply_updates",
                            lambda params, updates: params)
    else:
        sound = learner.TPULearner._loss_fn

        def half(self, logits, y, w):
            import jax.numpy as jnp
            keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2)
            return sound(self, logits, y, w * keep)
        monkeypatch.setattr(learner.TPULearner, "_loss_fn", half)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_cell_catches_a_broken_step(tmp_path, monkeypatch, fault):
    _break_learner(monkeypatch, fault)
    root = make_root(tmp_path)
    line = run.run_cell(root, "gpt2m_train", 11, 1.0, False,
                        require_tpu=False)
    assert line["correct"] is False
    over = {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert {"loss_mean_gap", "change_gap"} <= over
    if fault == "state_unchanged":
        # no leaf moved: every leaf's gap is the whole of its norm
        assert check(line, "change_gap") == pytest.approx(1.0)
        assert "loss2_gap" in over


def test_train_change_gaps_against_the_leaf_or_the_median_leaf():
    train = run.load_module(os.path.join(BENCH_DIR, "drivers", "train.py"))
    want = {"a": 1.0, "b": 2.0, "c": 0.01, "dead": 0.0}
    got = {"a": 1.1, "b": 1.9, "c": 0.02, "dead": 0.0}
    # 'dead' does not move in the reference and is left out; 'c' is held
    # against the median leaf's norm (1.0), not its own
    assert train.change_gaps(got, want) == pytest.approx(
        {"a": 0.1, "b": 0.05, "c": 0.01})
    assert train.change_gaps({**got, "b": 0.0}, want)["b"] == 1.0


@pytest.fixture(scope="module")
def serve_line(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("serve"))
    return run.run_cell(root, "gpt2xl_serve_steady", 2 ** 31 + 9, 1.5,
                        False, require_tpu=False)


def test_serve_cell_end_to_end(serve_line):
    line = serve_line
    assert line["correct"] is True
    assert line["attempted"] == 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] <= \
        line["metrics"]["serve_p95_ms"]["value"]
    assert 0 < line["info"]["serve_mean_ms"] <= \
        line["metrics"]["serve_p95_ms"]["value"]
    assert line["info"]["recompiles"] == 0
    assert line["info"]["sampled"] == 8
    assert 1 <= line["info"]["classes_in_sample"] <= 8
    assert check(line, "served_not_model") == 0
    assert set(line["compared"]) == {"class_gap", "logit_rel_l2",
                                     "served_not_model", "unanswered"}


def test_serve_cell_catches_an_altered_answer(tmp_path, monkeypatch):
    from mmlspark_tpu.models import tpu_model
    sound = tpu_model._FlaxApply.__call__

    def altered(self, weights, inputs):
        import jax.numpy as jnp
        return jnp.roll(sound(self, weights, inputs), 1, axis=-1)
    monkeypatch.setattr(tpu_model._FlaxApply, "__call__", altered)
    root = make_root(tmp_path)
    line = run.run_cell(root, "gpt2xl_serve_steady", 13, 1.0, False,
                        require_tpu=False)
    assert line["correct"] is False
    assert check(line, "logit_rel_l2") > \
        line["compared"]["logit_rel_l2"]["limit"]


def test_serve_cell_catches_replies_swapped_within_a_batch(
        tmp_path, monkeypatch):
    from mmlspark_tpu.models import tpu_model
    sound = tpu_model._FlaxApply.__call__
    monkeypatch.setattr(
        tpu_model._FlaxApply, "__call__",
        lambda self, weights, inputs: sound(self, weights, inputs)[::-1])

    def full_batches(root, bench):
        # at a toy size a step is short and a batch holds one row: wait
        # until it holds eight, so that there are replies to swap
        path = os.path.join(root, "benchmark", "traffic",
                            "poisson_steady.json")
        mix = json.load(open(path))
        mix["max_wait_ms"] = 250.0
        json.dump(mix, open(path, "w"))
    root = make_root(tmp_path, full_batches)
    line = run.run_cell(root, "gpt2xl_serve_steady", 17, 1.0, False,
                        require_tpu=False)
    assert line["correct"] is False
    assert line["info"]["classes_in_sample"] > 1
    assert check(line, "class_gap") > line["compared"]["class_gap"]["limit"]


def test_serve_compare_counts_what_never_came():
    serve = run.load_module(os.path.join(BENCH_DIR, "drivers", "serve.py"))
    ref = np.array([[0.0, 1.0, 3.0], [2.0, 0.5, 0.1]])
    limits = {"class_gap": 0.5, "logit_rel_l2": 0.1, "served_not_model": 0,
              "unanswered": 0}
    ok = {c["name"]: c["value"] for c in serve.compare(
        [2, 0], ref + 0.01, ref, limits, 0)}
    assert ok["class_gap"] == 0 and ok["unanswered"] == 0
    assert ok["served_not_model"] == 0
    assert ok["logit_rel_l2"] < 0.01
    bad = {c["name"]: c["value"] for c in serve.compare(
        [1, 0], ref, ref, limits, 2)}
    assert bad["class_gap"] == 2.0 and bad["served_not_model"] == 1
    assert bad["unanswered"] == 2


# ------------------------------- the controls of ``correct``, at toy size

CONTROLS = {
    "gpt2m_train": {"sound": True, "fp8": False, "half_batch": False,
                    "state_unchanged": False},
    "gpt2xl_serve_steady": {"sound": True, "fp8": False, "swapped": False,
                            "altered": False},
}


@pytest.fixture(scope="module")
def control_readings(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("control"))
    out = {}
    for name, stand_ins in CONTROLS.items():
        cell = run.load_cell(root, name)
        cell["seconds"] = 1.0
        driver = run.load_module(os.path.join(
            cell["home"], "drivers", cell["traffic_file"]["driver"] + ".py"))
        out[name] = driver.control(cell, 3, list(stand_ins))
    return out


@pytest.mark.parametrize("cell,stand_in", [
    (c, s) for c, stand_ins in CONTROLS.items() for s in stand_ins])
def test_control_reads_not_correct_and_the_program_correct(
        control_readings, cell, stand_in):
    """The reference in the precision below bfloat16, or with a fault
    planted, in the program's place: ``correct`` comes out false by
    run.py's own rule; the program itself comes out true."""
    checks = control_readings[cell][stand_in]
    assert run.judge(checks) is CONTROLS[cell][stand_in], \
        run.compared(checks)


# ------------------------------------------------------ the entry point

def _run_py(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_py_refuses_to_measure_without_a_tpu():
    out = _run_py(ROOT, "--workload", "gpt2m_train", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "measures on a TPU" in out.stderr


def test_run_py_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), str(tmp_path / path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    out = _run_py(str(tmp_path), "--workload", "gpt2xl_serve_steady")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_run_py_unknown_workload():
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell(ROOT, "no_such_cell")


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert run.place_compile_cache(str(tmp_path)) == \
        str(tmp_path / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert run.place_compile_cache(str(tmp_path)) == "/somewhere/else"


# ------------------------------------- new files and entries, no edits

def test_a_cell_a_config_a_driver_and_a_metric_are_added_as_files(
        tmp_path):
    def extra(root, bench):
        home = os.path.join(root, "benchmark")
        before = {}
        for dirpath, _, files in os.walk(home):
            for f in files:
                p = os.path.join(dirpath, f)
                before[p] = open(p, "rb").read()
        json.dump({"networkSpec": {"width": 3}},
                  open(os.path.join(home, "configs", "dummy.json"), "w"))
        json.dump({"driver": "dummy", "answer": 42.0},
                  open(os.path.join(home, "traffic", "dummy_mix.json"),
                       "w"))
        with open(os.path.join(home, "drivers", "dummy.py"), "w") as f:
            f.write(
                "def run(cell, seed, seconds, trace_dir, t_start):\n"
                "    v = cell['traffic_file']['answer'] + seed\n"
                "    return {'end_to_end': {'dummy_rate': v,\n"
                "                           'setup_s': 0.5},\n"
                "            'attempted': 1, 'failed': 0,\n"
                "            'checks': [{'name': 'off', 'value': 0.0,\n"
                "                        'limit': 0.0}],\n"
                "            'memory_peak_bytes': 7,\n"
                "            'counters': {'width':\n"
                "                cell['config_file']['networkSpec']"
                "['width']}}\n")
        with open(os.path.join(home, "metrics", "dummy.count.py"),
                  "w") as f:
            f.write("def read(ctx):\n"
                    "    return ctx['counters']['width'] * 2\n")
        with open(os.path.join(home, "metrics", "dummy_silent.py"),
                  "w") as f:
            f.write("def read(ctx):\n    return None\n")
        bench["configs"].append({
            "name": "dummy", "source": "none", "reduced": [],
            "file": "benchmark/configs/dummy.json", "why": "a dummy"})
        bench["workloads"].append({
            "name": "dummy_cell", "config": "dummy",
            "traffic": "dummy_mix", "chips": 1, "why": "a dummy"})
        bench["end_to_end"].append({
            "name": "dummy_rate", "unit": "1/s", "better": "higher",
            "bound": 0.01, "source": "host_clock",
            "workloads": ["dummy_cell"]})
        for name in ("dummy.count", "dummy_silent"):
            bench["per_layer"].append({
                "name": name, "unit": "1", "better": "higher",
                "source": "program_counter", "layer": "dummy",
                "moves": "dummy_rate", "workloads": ["dummy_cell"]})
        for p, body in before.items():
            assert open(p, "rb").read() == body, f"{p} was edited"
    root = make_root(tmp_path, extra)
    line = run.run_cell(root, "dummy_cell", 8, 1.0, False,
                        require_tpu=False)
    assert line["correct"] is True
    assert line["metrics"] == {
        "dummy_rate": {"value": 50.0, "unit": "1/s"},
        "setup_s": {"value": 0.5, "unit": "s"}}
    assert line["device"]["memory_peak_bytes"] == 7
    cell = run.load_cell(root, "dummy_cell")
    assert [m["name"] for m in cell["per_layer"]] == \
        ["dummy.count", "dummy_silent"]
    got = run.read_per_layer(cell, {"counters": {"width": 3}})
    assert got == {"dummy.count": {"value": 6.0, "unit": "1"}}


# ------------------------------------------------ the per-layer readers

def _ctx(cell, trace, counters, peak=True):
    loaded = run.load_cell(ROOT, cell)
    return {"cell": loaded, "trace": trace, "counters": counters,
            "peak": flops.peaks("TPU v5 lite") if peak else None,
            "end_to_end": {}}


FLASH_OPS = {
    '%_flash_forward.3 = bf16[128,1024,64] custom-call(), '
    'custom_call_target="tpu_custom_call"': {"seconds": 0.02, "count": 24},
    '%_flash_backward.4 = bf16[128,1024,64] custom-call(), '
    'custom_call_target="tpu_custom_call"': {"seconds": 0.05, "count": 48},
    "%fusion.1 = bf16[8] fusion()": {"seconds": 0.5, "count": 100},
}
TRACE = {"busy_s": 0.9, "window_s": 1.0, "ops": FLASH_OPS, "gaps": [],
         "module_runs": 1}
# the served model is 48 layers deep: one forward call a layer and batch
SERVE_TRACE = {**TRACE, "ops": {
    k.replace(".3 =", ".5 ="): {"seconds": 0.04, "count": 48}
    for k in FLASH_OPS if "forward" in k}}
TRACES = {"gpt2m_train": TRACE, "gpt2xl_serve_steady": SERVE_TRACE}
TRAIN_COUNTERS = {"tokens_per_s": 45000.0, "seq": 1024, "batch": 8,
                  "steps_per_dispatch": 1}
SERVE_COUNTERS = {"rows_ok": 16, "seq": 1024, "bucket": 8,
                  "queue_wait_ms": 12.5, "device_wait_ms": 230.0}
READINGS = [
    ("train_step_mfu", "gpt2m_train", TRAIN_COUNTERS,
     100 * 45000 * (6 * (301_989_888 + 1024 * 50257)
                    + 6 * 1024 * 1024 * 24) / 197e12),
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     100 * 24 * 3 * (2 * 8 * 16 * 1024 * 1024 * 64 / 197e12) / 0.07),
    ("device_idle_train", "gpt2m_train", TRAIN_COUNTERS, 10.0),
    ("serve_queue_wait_ms", "gpt2xl_serve_steady", SERVE_COUNTERS, 12.5),
    ("scorer_device_wait_ms", "gpt2xl_serve_steady", SERVE_COUNTERS,
     230.0),
    ("serve_forward_mfu", "gpt2xl_serve_steady", SERVE_COUNTERS,
     100 * 16 * 3_180_960_204_800 / (0.9 * 197e12)),
    ("flash_serve_roofline", "gpt2xl_serve_steady", SERVE_COUNTERS,
     100 * 48 * (2 * 8 * 25 * 1024 * 1024 * 64 / 197e12) / 0.04),
    ("device_idle_serve", "gpt2xl_serve_steady", SERVE_COUNTERS, 10.0),
]


@pytest.mark.parametrize("name,cell,counters,want", READINGS,
                         ids=[r[0] for r in READINGS])
def test_per_layer_reader_by_hand(name, cell, counters, want):
    reader = run.load_module(os.path.join(BENCH_DIR, "metrics",
                                          name + ".py"))
    assert reader.read(_ctx(cell, TRACES[cell], counters)) == \
        pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name,cell", [(r[0], r[1]) for r in READINGS],
                         ids=[r[0] for r in READINGS])
def test_per_layer_reader_with_nothing_to_read_returns_nothing(name, cell):
    reader = run.load_module(os.path.join(BENCH_DIR, "metrics",
                                          name + ".py"))
    empty = {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": [],
             "module_runs": 0}
    for trace in (None, empty):
        assert reader.read(_ctx(cell, trace, {}, peak=False)) is None


OTHER_KERNEL = {'%_hist.1 = f32[64] custom-call(), custom_call_target='
                '"tpu_custom_call"': {"seconds": 0.01, "count": 24}}


@pytest.mark.parametrize("name,cell,counters,ops", [
    # another Pallas kernel in the step is not read as flash
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     {**FLASH_OPS, **OTHER_KERNEL}),
    # a backward fused into one call a layer keeps its cost a layer
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     {k: ({"seconds": 0.05, "count": 24} if "backward" in k else v)
      for k, v in FLASH_OPS.items()}),
], ids=["other_kernel", "fused_backward"])
def test_flash_roofline_reads_the_flash_calls_alone(name, cell, counters,
                                                    ops):
    reader = run.load_module(os.path.join(BENCH_DIR, "metrics",
                                          name + ".py"))
    want = dict(r[::3] for r in READINGS)[name]
    assert reader.read(_ctx(cell, {**TRACE, "ops": ops}, counters)) == \
        pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name,cell,counters,ops", [
    # a forward call short of one a layer: part of the work is out of sight
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     {k: ({"seconds": 0.02, "count": 23} if "forward" in k else v)
      for k, v in FLASH_OPS.items()}),
    # backward calls that are no whole number a layer
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     {k: ({"seconds": 0.05, "count": 47} if "backward" in k else v)
      for k, v in FLASH_OPS.items()}),
    # the kernel gone from the step
    ("flash_train_roofline", "gpt2m_train", TRAIN_COUNTERS,
     {"%fusion.1 = bf16[8] fusion()": {"seconds": 0.5, "count": 100}}),
    ("flash_serve_roofline", "gpt2xl_serve_steady", SERVE_COUNTERS,
     {k: ({"seconds": 0.02, "count": 96} if "forward" in k else v)
      for k, v in FLASH_OPS.items()}),
], ids=["forward_short", "backward_odd", "no_kernel", "serve_double"])
def test_flash_roofline_is_silent_when_the_calls_do_not_add_up(
        name, cell, counters, ops):
    reader = run.load_module(os.path.join(BENCH_DIR, "metrics",
                                          name + ".py"))
    assert reader.read(_ctx(cell, {**TRACE, "ops": ops}, counters)) is None


# -------------------------------------------------------- BENCHMARK.json

def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry_is_well_formed(metric):
    per_layer = metric in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    listed = set(metric.get("workloads", cells))
    assert listed and listed <= cells
    if per_layer:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "metrics", metric["name"] + ".py"))
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # every cell that reports it reports the metric it moves
        assert listed <= set(moved.get("workloads", cells))
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1


def test_names_are_unique_and_setup_is_reported_everywhere():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, cell["name"])
    mix = loaded["traffic_file"]
    assert os.path.isfile(os.path.join(BENCH_DIR, "drivers",
                                       mix["driver"] + ".py"))
    assert mix["who"] and mix["what"] and mix["limits"]
    reported = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_entry_and_its_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    body = json.load(open(os.path.join(ROOT, config["file"])))
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] == []
    spec, pub = body["networkSpec"], body["published"]
    # no width or depth differs from the published config
    assert (spec["dim"], spec["depth"], spec["heads"], spec["max_len"],
            spec["vocab_size"]) == (pub["n_embd"], pub["n_layer"],
                                    pub["n_head"], pub["n_positions"],
                                    pub["vocab_size"])
    assert body["departures"] and body["precision"]


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert ok.match(rel), rel
