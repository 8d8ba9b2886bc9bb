"""Flagship benchmarks: CIFAR-10 ConvNet training throughput (the
cntk-train headline path) + HIGGS-shaped GBDT training wall-clock (the
lightgbm headline path). BASELINE.json names exactly these two.

CIFAR (ref: notebooks/gpu/401 — BrainScript ConvNet on 32x32x3 CIFAR-10,
parallelTrain on a 4-GPU Azure N-series VM). The reference publishes no
absolute numbers, so the primary vs_baseline constant is the
commonly-reported single-K80 CNTK ConvNet throughput for that hardware
class, ~1000 imgs/sec. A measured in-image torch-CPU baseline (run
``python tools/measure_baseline.py``, stored in BASELINE.json under
"measured") is reported alongside when present.

The training feed is DEVICE-RESIDENT (``TPULearner(dataFeed='device')``):
the padded dataset lives in HBM, each epoch is shuffled on device, and the
steady-state step consumes only a scalar index — so the number measures
the chip, not host feed scheduling. MFU is computed from XLA's own
cost-analysis FLOPs of the compiled train step against the chip's bf16
peak (imgs/sec stays the headline; MFU makes it auditable).

A ResNet-20 config (the notebook-301/401 model family) runs as a second
training metric. Both CIFAR models are structurally MXU-lane-underfilled
(16-64 output channels vs 128 lanes — see docs/perf_analysis.md), so a
Transformer-LM config (dim 2048, 8 layers, seq 1024, vocab 32k, flash
attention, bf16 head) runs as the third: the model where the MXU gets
real work. Its MFU is the headline utilization number.

GBDT (ref: docs/lightgbm.md:16-18 — LightGBM-on-Spark "10-30% faster"
than SparkML GBT on HIGGS, no absolute number). Config mirrors the
LightGBM HIGGS benchmark shape: 1M rows x 28 features, binary objective,
63 leaves, 63 bins, 40 iterations. vs_baseline prefers the MEASURED
in-image sklearn HistGradientBoosting wall-clock on the identical config
(BASELINE.json "measured"); the historical ~35 s LightGBM-CPU constant is
the fallback and stays in the JSON as context. Wall-clock vs_baseline is
baseline/ours, so >= 1.0 means we are faster.

Prints ONE JSON line: the CIFAR headline with the other results under
"secondary". Runs on whatever jax.devices() provides. One process per
chip: scenarios whose point is a fresh process ON the device
(coldstart) run before this process touches jax; every other child
process is pinned to the CPU.
"""

import json
import os
import time

import numpy as np

from mmlspark_tpu.utils.compile_cache import configure_compile_cache

# Azure N-series (K80-class) CNTK ConvNet throughput, imgs/sec/GPU — the
# reference's notebook-401 hardware (no absolute number published; see
# BASELINE.md).
BASELINE_IMGS_PER_SEC_PER_CHIP = 1000.0

# native LightGBM, 16-core CPU node, 1M x 28 HIGGS subsample, 63 leaves /
# 63 bins / 40 iters (docs/lightgbm.md publishes no absolute number; see
# module docstring). Fallback when no measured baseline exists.
BASELINE_HIGGS_WALL_S = 35.0

BATCH = 1024
# 128 steps/epoch: each epoch is ONE device dispatch (lax.scan chunk).
# Chunk dispatches queue asynchronously; the only fixed cost in the
# timed window is the final sync, which more timed chunks amortize.
# 9 epochs = 1 warmup (compile+sync) + 8 timed chunks.
STEPS_PER_EPOCH = 128
EPOCHS = 9

HIGGS_N, HIGGS_F = 1_000_000, 28
HIGGS_VALID_N = 100_000


def _cpu_child_env() -> dict:
    """Environment of a child whose result is a CPU-side count or wall:
    a chip belongs to one process, and this one may hold it."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def _measured_baselines() -> dict:
    """Measured baselines from BASELINE.json — only if they were measured
    on THIS machine (else a different box's numbers would masquerade as a
    measured-vs-measured comparison; rerun tools/measure_baseline.py)."""
    import platform
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE.json")
    try:
        with open(path) as f:
            measured = json.load(f).get("measured", {})
    except Exception:
        return {}
    here = f"{platform.machine()}, {os.cpu_count()} cores"
    if measured.get("machine") != here:
        print(f"# measured baselines are from {measured.get('machine')!r}, "
              f"this is {here!r}; falling back to documented constants",
              flush=True)
        return {}
    return measured


def _train_throughput(network_spec: dict) -> dict:
    """Train on synthetic CIFAR-shaped data with the device-resident feed;
    return imgs/sec/chip + MFU from the learner's own timing."""
    import jax

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.learner import TPULearner
    from mmlspark_tpu.parallel import mesh as mesh_lib

    n_chips = len(jax.devices())
    mesh = mesh_lib.make_mesh({"data": n_chips})

    rng = np.random.default_rng(0)
    n = BATCH * STEPS_PER_EPOCH
    x = rng.integers(0, 256, size=(n, 32, 32, 3)).astype(np.float32) / 255.0
    y = rng.integers(0, 10, size=n).astype(np.int64)
    table = DataTable({"features": x.reshape(n, -1), "label": y})

    learner = TPULearner(
        networkSpec=network_spec,
        inputShape=[32, 32, 3],
        batchSize=BATCH, learningRate=0.1, computeDtype="bfloat16",
        epochs=EPOCHS, logEvery=10_000, dataFeed="device")
    learner.set_mesh(mesh)
    learner.fit(table)

    t = learner.timing
    out = {
        "imgs_per_sec_per_chip": t["examples_per_sec"] / n_chips,
        "steps_timed": t["steps_timed"],
    }
    if "tflops_per_sec_per_chip" in t:
        out["tflops_per_sec_per_chip"] = round(t["tflops_per_sec_per_chip"], 2)
    if "mfu" in t:
        out["mfu"] = round(t["mfu"], 4)
    return out


def bench_cifar() -> dict:
    # notebook-401 ConvNet shape: 3 conv layers + dense, bf16 on the MXU
    return _train_throughput(
        {"type": "convnet", "conv_features": [64, 64, 64],
         "dense_features": [256], "num_classes": 10})


def bench_resnet() -> dict:
    # notebook-301/401 model family: CIFAR ResNet-20 (stage_sizes 3,3,3)
    return _train_throughput(
        {"type": "resnet", "stage_sizes": [3, 3, 3], "width": 16,
         "num_classes": 10})


# LM config: GPT-2-medium-class width. dim 2048 fills the MXU's 128
# lanes 16x over; the vocab projection runs bf16 (head_dtype) and the
# attention path is the Pallas flash kernel (L=1024 >= FLASH_MIN_LEN).
LM_BATCH, LM_SEQ = 8, 1024
LM_SPEC = {"type": "transformer", "vocab_size": 32000, "dim": 2048,
           "depth": 8, "heads": 16, "max_len": LM_SEQ,
           "head_dtype": "bfloat16"}


def bench_lm() -> dict:
    """Decoder-only LM training — the config where the MXU gets real
    work (docs/perf_analysis.md §4). Next-token prediction on synthetic
    token streams; the quality gates for the transformer live in
    tests/test_benchmarks.py, this measures the chip."""
    import jax

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.learner import TPULearner
    from mmlspark_tpu.parallel import mesh as mesh_lib

    n_chips = len(jax.devices())
    mesh = mesh_lib.make_mesh({"data": n_chips})
    rng = np.random.default_rng(0)
    n = LM_BATCH * 16
    toks = rng.integers(0, LM_SPEC["vocab_size"],
                        size=(n, LM_SEQ)).astype(np.float32)
    tgts = np.roll(toks.astype(np.int64), -1, axis=1)
    table = DataTable({"features": toks, "label": tgts})
    learner = TPULearner(
        networkSpec=LM_SPEC, loss="token_cross_entropy",
        batchSize=LM_BATCH, learningRate=1e-3, optimizer="adamw",
        computeDtype="bfloat16", epochs=5, logEvery=10_000,
        dataFeed="device")  # 1 warmup chunk + 4 timed chunks
    learner.set_mesh(mesh)
    learner.fit(table)
    t = learner.timing
    out = {
        "tokens_per_sec_per_chip": t["examples_per_sec"] * LM_SEQ / n_chips,
        "steps_timed": t["steps_timed"],
    }
    if "tflops_per_sec_per_chip" in t:
        out["tflops_per_sec_per_chip"] = round(t["tflops_per_sec_per_chip"], 2)
    if "mfu" in t:
        out["mfu"] = round(t["mfu"], 4)
    return out


def bench_higgs_gbdt():
    """Timed HIGGS-shaped training at BOTH 63 bins (the LightGBM HIGGS
    benchmark config, headline) and 255 bins (the engine default —
    exercises the Pallas kernel's larger VMEM tiling band). Each wall
    comes with the booster's per-phase breakdown (bin/ship[/bin_device]/
    first_iter/boost/fetch) plus the ingest path (bin_device vs
    bin_host) and fused-chunk length, so driver-side drift is
    attributable to a phase. The 63-bin config also runs once with
    device binning forced OFF so the device-vs-host ingest saving is
    measured, not assumed."""
    from sklearn.metrics import roc_auc_score

    from mmlspark_tpu.gbdt.booster import train

    rng = np.random.default_rng(0)
    n = HIGGS_N + HIGGS_VALID_N
    X = rng.normal(size=(n, HIGGS_F)).astype(np.float32)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2]
             + 0.5 * np.sin(3 * X[:, 3])
             + rng.normal(scale=0.5, size=n))
    y = (logit > 0).astype(np.float64)
    Xtr, ytr = X[:HIGGS_N], y[:HIGGS_N]
    Xte, yte = X[HIGGS_N:], y[HIGGS_N:]

    def _timed(params):
        # one-chunk warmup at the FULL training shape isolates XLA
        # compile from the measured train (jit caches are shape-keyed;
        # the explicit boost_chunk=8 compiles the SAME fused-chunk
        # program the 40-iteration measured run dispatches — a 1-iter
        # warmup would compile the length-1 chunk instead and leave the
        # measured wall paying the length-8 compile)
        train({**params, "num_iterations": 8, "boost_chunk": 8},
              Xtr, ytr)
        t0 = time.time()
        booster = train(params, Xtr, ytr)
        wall = time.time() - t0
        entry = {"wall_s": round(wall, 2),
                 "phases": booster.train_timing,
                 "bin_path": booster.train_info.get("bin_path"),
                 "boost_chunk": booster.train_info.get("boost_chunk")}
        return entry, booster

    out = {}
    auc = None
    for max_bin in (63, 255):
        params = {"objective": "binary", "num_iterations": 40,
                  "num_leaves": 63, "max_bin": max_bin,
                  "min_data_in_leaf": 50}
        out[max_bin], booster = _timed(params)
        if max_bin == 63:
            auc = roc_auc_score(yte, booster.predict(Xte))
            hist_method = booster.params["hist_method"]
            # host-binning comparison point: same config, ingest forced
            # to the host kernels (bin+ship delta = the device saving)
            out["host_bin_63"], _ = _timed(
                {**params, "device_binning": "off"})
    return out, auc, hist_method


AUTOML_N = 1_000_000
AUTOML_HASH_WIDTH = 64     # dense hashed block: 1M x 64 f32 = 256 MB
AUTOML_CANDIDATES = 8
AUTOML_TUNE_ROWS = 200_000  # CV sweep on a subsample (standard AutoML
#                             practice; featurization is the 1M headline)


def bench_automl() -> dict:
    """AutoML hot path: a 1M-row mixed numeric/string/token table runs
    Featurize (columnar kernels) against the RETAINED row-loop
    reference — both measured, outputs bit-compared — then a
    random-search tune of a linear model over the featurized table
    exercises the fold-cached, device-batched CV sweep. Reports walls,
    the vectorization speedup, the tune search path (vmap dispatches vs
    serial), and the automl phase-histogram breakdown."""
    from mmlspark_tpu.automl.featurize import Featurize
    from mmlspark_tpu.automl.tuning import (
        HyperparamBuilder, RandomSpace, RangeHyperParam,
        TuneHyperparameters,
    )
    from mmlspark_tpu.core import metrics as MCmod
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.linear import TPULogisticRegression

    rng = np.random.default_rng(0)
    n = AUTOML_N
    x1 = rng.normal(size=n)
    x1[rng.random(n) < 0.01] = np.nan       # NaN-imputation path engaged
    x2 = rng.uniform(size=n)
    colors = [f"c{i:02d}" for i in range(12)]
    color = [colors[i] for i in rng.integers(0, 12, n)]
    words = [f"token{i:04d}" for i in range(2000)]
    lens = rng.integers(5, 13, n)
    tok_ids = rng.integers(0, len(words), int(lens.sum()))
    toks, pos = [], 0
    for ln in lens:
        toks.append([words[j] for j in tok_ids[pos:pos + ln]])
        pos += int(ln)
    label = ((np.nan_to_num(x1) + x2) > 0.5).astype(np.float64)
    table = DataTable({"x1": x1, "x2": x2, "color": color, "toks": toks,
                       "label": label})

    feat = Featurize(featureColumns=["x1", "x2", "color", "toks"],
                     numberOfFeatures=AUTOML_HASH_WIDTH)
    t0 = time.time()
    model = feat.fit(table)
    fit_s = time.time() - t0
    # warm both paths on a small slice (pyarrow's first conversion
    # lazily initializes ~1.5s of machinery; measure kernels, not init)
    warm = DataTable({c: table[c][:4096] for c in table.column_names})
    model.transform(warm)
    model.transform_rowloop(warm)
    # min of 2 reps per path: this shared host class swings 1.2-1.5x
    # run to run, and min-of-reps is the standard de-noising for both
    # sides of the ratio
    vec_s, out = 1e18, None
    for _ in range(2):
        t0 = time.time()
        out = model.transform(table)
        vec_s = min(vec_s, time.time() - t0)
    rowloop_s, ref = 1e18, None
    for _ in range(2):
        t0 = time.time()
        ref = model.transform_rowloop(table)
        rowloop_s = min(rowloop_s, time.time() - t0)
    bit_identical = bool(np.array_equal(out["features"],
                                        ref["features"]))
    del ref

    space = (HyperparamBuilder()
             .add_hyperparam("stepSize",
                             RangeHyperParam(0.05, 1.0, log=True))
             .add_hyperparam("regParam",
                             RangeHyperParam(1e-5, 1e-2, log=True))
             .build())
    tuner = TuneHyperparameters(
        models=[TPULogisticRegression(maxIter=20)],
        paramSpace=RandomSpace(space, seed=0),
        evaluationMetric="accuracy", numFolds=3,
        numRuns=AUTOML_CANDIDATES, seed=0)
    k = AUTOML_TUNE_ROWS
    tune_table = DataTable({"features": out["features"][:k],
                            "label": label[:k]})
    t0 = time.time()
    tuned = tuner.fit(tune_table)
    tune_s = time.time() - t0

    phases = {k: h.summary()
              for k, h in MCmod.automl_histograms().items()}
    return {
        "metric": "automl_featurize_1m_vectorization_speedup",
        "value": round(rowloop_s / vec_s, 1) if vec_s else None,
        "unit": "x (rowloop wall / columnar wall, same table)",
        "featurize_fit_s": round(fit_s, 2),
        "featurize_transform_s": round(vec_s, 2),
        "featurize_rowloop_s": round(rowloop_s, 2),
        "bit_identical": bit_identical,
        "tune_wall_s": round(tune_s, 2),
        "tune_search": tuned.search_info,
        "tune_best_metric": round(float(tuned.get("bestMetric")), 4),
        "phases": phases,
        "config": (f"{n} rows x (2 numeric + 12-level string + 5-12 "
                   f"token lists of 9-char words), hash width "
                   f"{AUTOML_HASH_WIDTH}, {AUTOML_CANDIDATES} logistic "
                   f"candidates x 3 folds on {k} rows"),
    }


PIPELINE_N = 1_000_000
PIPELINE_FIT_N = 100_000
PIPELINE_HASH_WIDTH = 32
# one-hot string block: the wide part. 128 levels is an ordinary
# categorical width, and it is exactly where stage-at-a-time hurts: the
# host path materializes the (N, 128) one-hot + the assembled + the
# scaled + the f64 copies, while the fused program ships a 4 MB i32
# code vector and keeps every wide intermediate an XLA buffer.
PIPELINE_LEVELS = 128


def bench_pipeline() -> dict:
    """Whole-pipeline fusion (core/fusion.py): 1M raw rows (numerics
    with NaN, a 128-level string, token lists) scored through
    Featurize -> StandardScaler -> logistic -> DropColumns(features),
    three ways:

    - **staged_host** — ``PipelineModel.transform``: the legacy
      stage-at-a-time path (host columnar featurize, f64 numpy model
      math, full intermediate materialization between stages);
    - **staged_device** — the SAME device kernels dispatched one stage
      at a time with a host round trip between every stage;
    - **fused** — one XLA program per device-capable run, host kernels
      (string codes / token hashing) feeding it directly, ONE D2H round
      trip. Measured COLD (fresh table identity: host feed kernels +
      H2D paid every rep) and WARM (device-resident DeviceTable:
      columns/feeds shipped once, repeats pay dispatch + fetch only).

    Parity is checked in-line: fused == staged_device bit-identical,
    predictions == staged_host exactly. Recompiles across reps and
    device round trips per transform are reported (the zero-retrace /
    one-round-trip acceptance evidence)."""
    from mmlspark_tpu.automl.featurize import Featurize
    from mmlspark_tpu.core import metrics as MCmod
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.linear import TPULogisticRegression
    from mmlspark_tpu.core.stage import Pipeline
    from mmlspark_tpu.stages.basic import DropColumns
    from mmlspark_tpu.stages.dataprep import StandardScaler

    rng = np.random.default_rng(0)
    n = PIPELINE_N
    x1 = rng.normal(size=n)
    x1[rng.random(n) < 0.01] = np.nan
    x2 = rng.uniform(size=n)
    colors = [f"c{i:02d}" for i in range(PIPELINE_LEVELS)]
    color = [colors[i] for i in rng.integers(0, PIPELINE_LEVELS, n)]
    words = [f"tok{i:04d}" for i in range(800)]
    lens = rng.integers(3, 7, n)
    tok_ids = rng.integers(0, len(words), int(lens.sum()))
    toks, pos = [], 0
    for ln in lens:
        toks.append([words[j] for j in tok_ids[pos:pos + ln]])
        pos += int(ln)
    label = ((np.nan_to_num(x1) + x2) > 0.5).astype(np.float64)
    table = DataTable({"x1": x1, "x2": x2, "color": color,
                       "toks": toks, "label": label})

    t0 = time.time()
    pm = Pipeline(stages=[
        Featurize(featureColumns=["x1", "x2", "color", "toks"],
                  numberOfFeatures=PIPELINE_HASH_WIDTH,
                  oneHotEncodeCategoricals=True),
        StandardScaler(inputCol="features", outputCol="features"),
        TPULogisticRegression(featuresCol="features", labelCol="label",
                              maxIter=40),
        DropColumns(cols=["features"]),
    ]).fit(table.slice(0, PIPELINE_FIT_N))
    fit_s = time.time() - t0
    fused = pm.fused()

    # warm every path on a small slice: compiles + pyarrow lazy init
    # are measured nowhere below
    warm = table.slice(0, 4096)
    pm.transform(warm)
    fused.transform(warm)
    fused.transform_staged(warm)

    def fresh_view(t):
        # same column buffers, NEW table identity: the DeviceTable is
        # cold, so the rep pays host feed kernels + H2D like a fresh
        # batch of data would
        return DataTable({c: t.column(c) for c in t.column_names},
                         t.schema)

    # one untimed full-shape fused run: the 1M-row executable compiles
    # HERE, so the timed reps below prove zero steady-state recompiles
    fused.transform(fresh_view(table))

    def best(fn, reps=2):
        w, out = 1e18, None
        for _ in range(reps):
            t1 = time.time()
            out = fn()
            w = min(w, time.time() - t1)
        return w, out

    host_s, out_h = best(lambda: pm.transform(fresh_view(table)))
    staged_s, out_d = best(
        lambda: fused.transform_staged(fresh_view(table)))
    misses_before = fused.jit_cache_misses
    cold_s, out_f = best(lambda: fused.transform(fresh_view(table)))
    warm_s, _ = best(lambda: fused.transform(table), reps=3)
    recompiles = fused.jit_cache_misses - misses_before
    plan = fused.plan_for(table.schema)

    check_cols = ("rawPrediction", "probability", "prediction")
    bit_identical = all(
        np.array_equal(np.asarray(out_f[c]), np.asarray(out_d[c]))
        for c in check_cols)
    pred_equal_host = bool(np.array_equal(
        np.asarray(out_f["prediction"]), np.asarray(out_h["prediction"])))
    phases = {k: h.summary()
              for k, h in MCmod.pipeline_histograms().items()}
    return {
        "metric": "pipeline_fusion_speedup_vs_stage_at_a_time",
        "value": round(host_s / cold_s, 2) if cold_s else None,
        "unit": "x (legacy staged wall / fused COLD wall, same rows)",
        "warm_speedup": round(host_s / warm_s, 2) if warm_s else None,
        "staged_host_s": round(host_s, 2),
        "staged_device_s": round(staged_s, 2),
        "fused_cold_s": round(cold_s, 2),
        "fused_warm_s": round(warm_s, 2),
        "fit_s": round(fit_s, 2),
        "bit_identical_vs_staged_device": bit_identical,
        "prediction_equal_vs_staged_host": pred_equal_host,
        "steady_state_recompiles": recompiles,
        "device_roundtrips_per_transform": plan.last_roundtrips,
        "fusion_plan": plan.describe(),
        "phases": phases,
        "config": (f"{n} raw rows x (2 numeric w/ NaN + "
                   f"{PIPELINE_LEVELS}-level one-hot string + 3-6 token "
                   f"lists, hash {PIPELINE_HASH_WIDTH}) -> Featurize -> "
                   f"StandardScaler -> logistic(40 iters) -> "
                   f"drop(features); fit on {PIPELINE_FIT_N} rows"),
    }


SERVING_REQUESTS = 400
SERVING_CLIENTS = 16
SERVING_FEATURE_DIM = 128


# batching deadline: on a saturated small host, 6 ms collects 2-3x the
# rows of a 3 ms window and LOWERS p50 (fewer, fuller batches cost less
# total CPU per request); idle-path latency stays ~wait + service
SERVING_MAX_WAIT_MS = 6.0


def bench_serving() -> dict:
    """Model serving QPS + latency percentiles: a TPUModel (MLP scorer)
    behind a 2-engine ServingFleet, sprayed by concurrent clients — the
    reference's headline streaming/serving capability measured, not just
    proven correct (ref: DistributedHTTPSource.scala:96-266).

    The hot path under test: adaptive micro-batching (flush on
    batch-full OR 3 ms deadline), shape-bucketed pre-compiled
    executables (explicit warmup, zero steady-state recompiles), and
    the batcher-thread decode/pad stage overlapping device execution.
    Reports the per-stage latency breakdown from the engines' own
    histograms plus the steady-state recompile count."""
    import concurrent.futures

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import ServingFleet, json_scoring_pipeline

    import jax

    module = build_network({"type": "mlp", "features": [256, 128],
                            "num_classes": 10})
    rng = np.random.default_rng(0)
    x0 = np.zeros((1, SERVING_FEATURE_DIM), np.float32)
    weights = {"params": module.init(
        jax.random.PRNGKey(0), x0)["params"]}
    model = TPUModel(modelFn=lambda w, ins: module.apply(
        {"params": w["params"]}, list(ins.values())[0]),
        weights=weights, inputCol="features", outputCol="scores",
        batchSize=256, computeDtype="float32")

    # explicit warmup: every shape bucket compiles BEFORE the fleet
    # takes traffic, so no live request pays an XLA compile
    model.warmup({"features": x0})

    fleet = ServingFleet(json_scoring_pipeline(model), n_engines=2,
                         base_port=18800, batch_size=256, workers=2,
                         max_wait_ms=SERVING_MAX_WAIT_MS)
    # encode ONCE: a 128-float json.dumps per request would bill ~0.5 ms
    # of client-side CPU to the serving number on a small host
    payload = json.dumps(
        {"features": rng.normal(size=SERVING_FEATURE_DIM).tolist()}
    ).encode()

    def post(_i):
        t0 = time.perf_counter()
        body = fleet.post(payload, timeout=60)   # round-robin client
        assert "prediction" in body, body
        return (time.perf_counter() - t0) * 1e3

    try:
        for _ in fleet.addresses:            # warmup: first live batches
            post(0)
        misses_before = model.jit_cache_misses
        lat = []
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(SERVING_CLIENTS) as ex:
            futs = [ex.submit(post, i) for i in range(SERVING_REQUESTS)]
            for f in concurrent.futures.as_completed(futs):
                lat.append(f.result())
        wall = time.perf_counter() - t0
        recompiles = model.jit_cache_misses - misses_before
        agg = fleet.metrics()["aggregate"]
    finally:
        fleet.stop_all()
    lat = np.asarray(lat)

    def _p50(name):
        return agg.get(name, {}).get("p50", None)

    stage = agg.get("pipeline_stage", {})
    return {
        "metric": "serving_fleet_qps",
        "value": round(SERVING_REQUESTS / wall, 1),
        "unit": "requests/sec",
        "p50_ms": round(float(np.percentile(lat, 50)), 1),
        "p99_ms": round(float(np.percentile(lat, 99)), 1),
        "steady_state_recompiles": recompiles,
        "buckets": model.bucket_sizes(),
        "breakdown_p50_ms": {
            "queue_wait": _p50("queue_wait_ms"),
            "decode": _p50("decode_ms"),
            "pad": stage.get("pad_ms", {}).get("p50", None),
            "device": stage.get("device_ms", {}).get("p50", None),
            "pipeline": _p50("pipeline_ms"),
            "respond": _p50("respond_ms"),
            "batch_rows": _p50("batch_rows"),
        },
        "config": (f"{SERVING_REQUESTS} reqs, {SERVING_CLIENTS} clients, "
                   f"2 engines x 2 workers, MLP-{SERVING_FEATURE_DIM} "
                   f"TPUModel, batch 256, max_wait "
                   f"{SERVING_MAX_WAIT_MS} ms"),
    }


INGRESS_ROWS = 1_000_000
INGRESS_DIM = 16
INGRESS_CHUNK = 1024
INGRESS_SERVE_ROWS = 16_384
INGRESS_ROWS_PER_REQ = 64


def bench_ingress() -> dict:
    """Columnar ingress vs the JSON oracle (io/columnar.py) — the
    wire-to-device zero-copy scenario.

    Two measurements, both on THIS container (backend-labeled):

    1. **Codec microbench, 1M rows**: the server-side host work
       (decode + batch assembly) of 1M feature rows arriving as
       1024-row requests, per codec — JSON rows (the oracle's
       ``json.loads`` + stack), msgpack-columns (zero-copy
       ``np.frombuffer`` views), Arrow IPC. Pure ingress cost, no
       model, no HTTP.

    2. **Single-replica serving**: the same TPUModel MLP behind ONE
       engine, sprayed by concurrent clients — JSON one-row requests
       (the pre-existing protocol) vs msgpack-columns 64-row record
       batches (the columnar client, ``fleet.post_columns``). Reports
       rows/sec both ways, the speedup, the ingress phase breakdown
       (negotiate/decode/assemble/pad p50s from /metrics), the host
       fraction of request p50, and the steady-state recompile count
       on the columnar path."""
    import concurrent.futures

    from mmlspark_tpu.core.metrics import (
        ingress_decode_histograms, ingress_histograms,
    )
    from mmlspark_tpu.io import columnar as CIN
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import (
        ServingFleet, json_scoring_pipeline,
    )

    import jax

    rng = np.random.default_rng(7)

    # -- 1. codec microbench at 1M rows ---------------------------------
    n_chunks = INGRESS_ROWS // INGRESS_CHUNK
    n_rows = n_chunks * INGRESS_CHUNK     # whole requests only
    feats = rng.normal(size=(n_rows, INGRESS_DIM))
    chunks = [feats[i * INGRESS_CHUNK:(i + 1) * INGRESS_CHUNK]
              for i in range(n_chunks)]

    def decode_json(bodies):
        # the oracle's decode: one row object per request
        return np.stack([
            np.asarray(json.loads(b.decode())["features"],
                       dtype=np.float32)
            for b in bodies])

    def decode_columnar(codec, bodies):
        return np.concatenate([
            np.asarray(CIN.decode_columnar(codec, b)
                       .columns["features"], dtype=np.float32)
            for b in bodies])

    codec_results = {}
    json_bodies = [json.dumps({"features": row.tolist()}).encode()
                   for row in feats[:INGRESS_CHUNK]]  # 1 chunk as rows
    t0 = time.perf_counter()
    ref = decode_json(json_bodies)
    json_row_wall = (time.perf_counter() - t0) * n_chunks  # scaled to 1M
    codec_results["json_rows"] = {
        "decode_assemble_s": round(json_row_wall, 2),
        "rows_per_s": round(n_rows / json_row_wall),
        "note": f"measured on {INGRESS_CHUNK} rows, scaled x{n_chunks}",
    }
    codecs = ["msgpack"] + (["arrow"] if CIN._pyarrow() else [])
    for codec in codecs:
        bodies = [CIN.encode_columns({"features": c}, codec=codec)[0]
                  for c in chunks]
        t0 = time.perf_counter()
        out = decode_columnar(codec, bodies)
        wall = time.perf_counter() - t0
        assert out.shape == (n_rows, INGRESS_DIM)
        np.testing.assert_array_equal(
            out[:INGRESS_CHUNK], ref)   # bit parity with the oracle
        codec_results[codec] = {
            "decode_assemble_s": round(wall, 3),
            "rows_per_s": round(n_rows / wall),
            "speedup_vs_json": round(json_row_wall / wall, 1),
        }
    del feats, chunks

    # -- 2. single-replica serving, JSON rows vs columnar batches -------
    module = build_network({"type": "mlp", "features": [256, 128],
                            "num_classes": 10})
    x0 = np.zeros((1, SERVING_FEATURE_DIM), np.float32)
    weights = {"params": module.init(
        jax.random.PRNGKey(0), x0)["params"]}
    model = TPUModel(modelFn=lambda w, ins: module.apply(
        {"params": w["params"]}, list(ins.values())[0]),
        weights=weights, inputCol="features", outputCol="scores",
        batchSize=256, computeDtype="float32")
    model.warmup({"features": x0})
    fleet = ServingFleet(json_scoring_pipeline(model), n_engines=1,
                         base_port=19000, batch_size=256, workers=2,
                         max_wait_ms=SERVING_MAX_WAIT_MS)
    x = rng.normal(size=(INGRESS_ROWS_PER_REQ, SERVING_FEATURE_DIM))
    json_payload = json.dumps(
        {"features": x[0].tolist()}).encode()
    col_payload, col_ct = CIN.encode_columns({"features": x})

    def run_side(post_one, n_requests, rows_per_req):
        lat = []

        def post(_i):
            t0 = time.perf_counter()
            body = post_one()
            assert "prediction" in body, body
            return (time.perf_counter() - t0) * 1e3

        for _ in range(4):
            post(0)     # warm the live path
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(SERVING_CLIENTS) as ex:
            futs = [ex.submit(post, i) for i in range(n_requests)]
            for f in concurrent.futures.as_completed(futs):
                lat.append(f.result())
        wall = time.perf_counter() - t0
        lat = np.asarray(lat)
        return {
            "rows_per_s": round(n_requests * rows_per_req / wall, 1),
            "qps": round(n_requests / wall, 1),
            "p50_ms": round(float(np.percentile(lat, 50)), 2),
            "p99_ms": round(float(np.percentile(lat, 99)), 2),
        }

    def _p50(hist):
        return round(hist.summary().get("p50", 0.0), 4)

    try:
        json_side = run_side(
            lambda: fleet.post(json_payload, timeout=60),
            SERVING_REQUESTS, 1)
        misses_before = model.jit_cache_misses
        # the phase histograms are process-wide: RESET between sides
        # so the columnar host-fraction is measured on the columnar
        # workload alone, not diluted by the JSON side's samples
        for h in ingress_histograms().values():
            h.reset()
        for h in ingress_decode_histograms().values():
            h.reset()
        model._hists["pad_ms"].reset()
        # pre-encoded payload, like the JSON side: the server-side
        # ingress is under test, not client-side encode CPU
        col_side = run_side(
            lambda: fleet.post(col_payload, timeout=60,
                               content_type=col_ct),
            INGRESS_SERVE_ROWS // INGRESS_ROWS_PER_REQ,
            INGRESS_ROWS_PER_REQ)
        recompiles = model.jit_cache_misses - misses_before
        ih = ingress_histograms()
        dh = ingress_decode_histograms()
        phases = {
            "negotiate": _p50(ih["negotiate"]),
            "assemble": _p50(ih["assemble"]),
            "decode": {c: _p50(h) for c, h in dh.items()},
        }
        stage = fleet.metrics()["aggregate"].get("pipeline_stage", {})
        pad_p50 = stage.get("pad_ms", {}).get("p50", 0.0) or 0.0
        phases["pad"] = round(pad_p50, 4)
        host_ms = (phases["negotiate"] + phases["assemble"]
                   + phases["decode"].get("msgpack", 0.0) + pad_p50)
        host_fraction = (host_ms / col_side["p50_ms"]
                         if col_side["p50_ms"] else 0.0)
    finally:
        fleet.stop_all()

    return {
        "metric": "columnar_ingress_rows_per_s",
        "value": col_side["rows_per_s"],
        "unit": "rows/sec (single replica, msgpack-columns, "
                f"{INGRESS_ROWS_PER_REQ}-row requests)",
        "codec_1m_rows": codec_results,
        "serving_json_rows": json_side,
        "serving_columnar": col_side,
        "serving_speedup_rows_per_s": round(
            col_side["rows_per_s"] / json_side["rows_per_s"], 2),
        "ingress_phase_p50_ms": phases,
        "host_fraction_of_p50": round(host_fraction, 4),
        "steady_state_recompiles": recompiles,
        "config": (f"codec bench {INGRESS_ROWS} rows x {INGRESS_DIM} f64"
                   f" in {INGRESS_CHUNK}-row requests; serving 1 engine"
                   f" x 2 workers, MLP-{SERVING_FEATURE_DIM}, "
                   f"{SERVING_REQUESTS} JSON 1-row reqs vs "
                   f"{INGRESS_SERVE_ROWS // INGRESS_ROWS_PER_REQ} "
                   f"msgpack {INGRESS_ROWS_PER_REQ}-row reqs, "
                   f"{SERVING_CLIENTS} clients"),
    }


OBS_REQUESTS = 400
OBS_REPS = 2


def bench_observability() -> dict:
    """Telemetry overhead on the serving hot path, three interleaved
    modes (best-of per mode so shared-host noise hits every side):

    - ``off``   — tracing, SLO engine, and flight recorder all off
      (the bare PR 2 hot path);
    - ``tracing`` — request tracing only (the PR 7 contract);
    - ``telemetry`` — the FULL default-on plane: tracing + windowed
      SLO recording/burn-rate evaluation + the always-on flight
      recorder (the PR 13 contract: ≤3% vs off, pinned by
      tests/test_perf_floors.py::TestTelemetryOverheadFloor alongside
      the tracing floor).

    Reports qps per mode, both overhead percentages, buffer/SLO/
    recorder state from the telemetry run, one exported trace's span
    coverage, and the /metrics exposition size."""
    import concurrent.futures

    from mmlspark_tpu.core.flightrecorder import FlightRecorder
    from mmlspark_tpu.core.trace import Tracer, to_chrome_trace
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import ServingFleet, json_scoring_pipeline

    import jax

    module = build_network({"type": "mlp", "features": [256, 128],
                            "num_classes": 10})
    rng = np.random.default_rng(0)
    x0 = np.zeros((1, SERVING_FEATURE_DIM), np.float32)
    weights = {"params": module.init(
        jax.random.PRNGKey(0), x0)["params"]}
    model = TPUModel(modelFn=lambda w, ins: module.apply(
        {"params": w["params"]}, list(ins.values())[0]),
        weights=weights, inputCol="features", outputCol="scores",
        batchSize=256, computeDtype="float32")
    model.warmup({"features": x0})
    payload = json.dumps(
        {"features": rng.normal(size=SERVING_FEATURE_DIM).tolist()}
    ).encode()

    def run_once(mode: str, base_port: int):
        tracing = mode in ("tracing", "telemetry")
        telemetry = mode == "telemetry"
        tracer = Tracer(enabled=True) if tracing else None
        recorder = FlightRecorder() if telemetry else False
        fleet = ServingFleet(json_scoring_pipeline(model), n_engines=2,
                             base_port=base_port, batch_size=256,
                             workers=2,
                             max_wait_ms=SERVING_MAX_WAIT_MS,
                             tracer=tracer, tracing=tracing,
                             slo=None if telemetry else False,
                             flight_recorder=recorder)
        try:
            def post(_i):
                body = fleet.post(payload, timeout=60)
                assert "prediction" in body, body
            for _ in fleet.addresses:
                post(0)
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    SERVING_CLIENTS) as ex:
                list(ex.map(post, range(OBS_REQUESTS)))
            wall = time.perf_counter() - t0
            extras = {}
            if tracing:
                extras["buffer"] = tracer.buffer.stats()
                traces = [t for t in tracer.buffer.traces()
                          if t.root.name == "request"
                          and t.root.end is not None]
                if traces:
                    tr = traces[-1]
                    child = [s for s in tr.spans()
                             if s is not tr.root and s.end is not None]
                    extras["sample_trace"] = {
                        "trace_id": tr.trace_id,
                        "wall_ms": round(tr.duration_ms, 3),
                        "spans": {s.name: round(s.duration_ms, 3)
                                  for s in child},
                        "span_coverage": round(
                            sum(s.duration_ms for s in child)
                            / max(tr.duration_ms, 1e-9), 3),
                        "chrome_events": len(to_chrome_trace(
                            [tr])["traceEvents"]),
                    }
                extras["metrics_exposition_lines"] = len(
                    fleet.metrics_text().splitlines())
            if telemetry:
                slo = fleet.engines[0].slo
                status = slo.status()
                extras["slo"] = {
                    "degraded": status["degraded"],
                    "error_rate_1m": status.get("error_rate_1m"),
                    "p99_ms_1m": status.get("p99_ms_1m"),
                    "requests_1m": status.get("requests_1m"),
                }
                extras["flight_recorder"] = recorder.stats()
                bundle = recorder.dump_bundle("bench")
                extras["bundle_trace_events"] = len(
                    bundle["traces"].get("traceEvents", []))
        finally:
            fleet.stop_all()
            if telemetry:
                recorder.close()
        return OBS_REQUESTS / wall, extras

    qps = {"off": 0.0, "tracing": 0.0, "telemetry": 0.0}
    extras_best: dict = {}
    port = 19000
    for _ in range(OBS_REPS):     # interleaved: noise hits every mode
        for mode in ("off", "tracing", "telemetry"):
            q, extras = run_once(mode, port)
            port += 40
            if q > qps[mode]:
                qps[mode] = q
                if mode == "telemetry":
                    extras_best = extras

    def pct(off, on):
        return round((off - on) / off * 100, 2) if off else None

    return {
        "metric": "serving_telemetry_overhead",
        "value": pct(qps["off"], qps["telemetry"]),
        "unit": "% qps lost with FULL telemetry on (tracing + "
                "windowed SLO + flight recorder; best-of interleaved "
                "reps)",
        "qps_tracing_off": round(qps["off"], 1),
        "qps_tracing_on": round(qps["tracing"], 1),
        "qps_telemetry_on": round(qps["telemetry"], 1),
        "tracing_overhead_pct": pct(qps["off"], qps["tracing"]),
        "telemetry_overhead_pct": pct(qps["off"], qps["telemetry"]),
        **extras_best,
        "config": (f"{OBS_REQUESTS} reqs x {OBS_REPS} reps per mode, "
                   f"{SERVING_CLIENTS} clients, 2 engines x 2 workers, "
                   f"MLP-{SERVING_FEATURE_DIM}, batch 256"),
    }


SWAP_REQUESTS = 600
SWAP_CLIENTS = 12


def bench_swap() -> dict:
    """Zero-downtime model lifecycle under steady load: a 2-engine
    fleet serving an MLP scorer takes one ROLLING SWAP to a refreshed
    model mid-run (warmup-before-cutover, canary, drain — see
    serving/lifecycle.py). Reports availability across the run, p99
    both overall and DURING the swap window, and the recompile count
    outside the two models' warmups (the zero-steady-state-recompiles
    contract must hold straight through a swap)."""
    import concurrent.futures
    import threading

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import ServingFleet, json_scoring_pipeline
    from mmlspark_tpu.serving.lifecycle import CanaryPolicy

    import jax

    module = build_network({"type": "mlp", "features": [256, 128],
                            "num_classes": 10})
    rng = np.random.default_rng(0)
    x0 = np.zeros((1, SERVING_FEATURE_DIM), np.float32)

    def make_model(seed):
        weights = {"params": module.init(
            jax.random.PRNGKey(seed), x0)["params"]}
        return TPUModel(modelFn=lambda w, ins: module.apply(
            {"params": w["params"]}, list(ins.values())[0]),
            weights=weights, inputCol="features", outputCol="scores",
            batchSize=256, computeDtype="float32")

    m1, m2 = make_model(0), make_model(1)
    m1.warmup({"features": x0})     # v1 pre-compiled before traffic
    fleet = ServingFleet(json_scoring_pipeline(m1), n_engines=2,
                         base_port=18900, batch_size=256, workers=2,
                         max_wait_ms=SERVING_MAX_WAIT_MS)
    payload = json.dumps(
        {"features": rng.normal(size=SERVING_FEATURE_DIM).tolist()}
    ).encode()
    swap_window = {}
    failures = [0]
    fail_lock = threading.Lock()

    def post(_i):
        t0 = time.perf_counter()
        try:
            body = fleet.post(payload, timeout=60)
            assert "prediction" in body, body
        except Exception:  # noqa: BLE001 — availability metric
            with fail_lock:
                failures[0] += 1
            return None
        return (t0, (time.perf_counter() - t0) * 1e3)

    try:
        for _ in fleet.addresses:
            post(0)
        failures[0] = 0   # priming posts don't count against the
        #                   measured window's availability
        misses_before = m1.jit_cache_misses + m2.jit_cache_misses
        lat = []
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(SWAP_CLIENTS) as ex:
            futs = [ex.submit(post, i) for i in range(SWAP_REQUESTS)]
            time.sleep(0.3)          # steady load established
            swap_t0 = time.perf_counter()
            report = fleet.rolling_swap(
                json_scoring_pipeline(m2), "v2",
                warmup_example={"features": x0},
                policy=CanaryPolicy(fraction=0.25, min_batches=4,
                                    decision_timeout_s=30))
            swap_t1 = time.perf_counter()
            for f in concurrent.futures.as_completed(futs):
                if f.result() is not None:
                    lat.append(f.result())
        wall = time.perf_counter() - t0
        # m2's warmup compiles are part of the SWAP (off the hot path);
        # subtract them via the model's own warmup-time counter delta
        recompiles = (m1.jit_cache_misses + m2.jit_cache_misses
                      - misses_before)
        warm_compiles = len(m2.bucket_sizes())
        swap_window.update(report)
    finally:
        fleet.stop_all()
    all_ms = np.asarray([ms for _, ms in lat])
    during = np.asarray([ms for t, ms in lat
                         if swap_t0 <= t <= swap_t1]) \
        if len(lat) else np.asarray([])
    total = SWAP_REQUESTS
    return {
        "metric": "serving_rolling_swap",
        "availability": round((total - failures[0]) / total, 4),
        "qps": round(total / wall, 1),
        "p99_ms": round(float(np.percentile(all_ms, 99)), 1)
        if len(all_ms) else None,
        "p99_during_swap_ms": round(float(np.percentile(during, 99)), 1)
        if len(during) else None,
        "swap_wall_s": round(swap_t1 - swap_t0, 2),
        "swap_report": {"ok": swap_window.get("ok"),
                        "completed": swap_window.get("completed"),
                        "rolled_back": swap_window.get("rolled_back")},
        "recompiles_total": recompiles,
        "recompiles_beyond_new_model_warmup": recompiles - warm_compiles,
        "config": (f"{SWAP_REQUESTS} reqs, {SWAP_CLIENTS} clients, "
                   f"2 engines, rolling swap mid-run, canary 25% / "
                   f"4 batches, MLP-{SERVING_FEATURE_DIM}"),
    }


QUANT_ROWS = 200_000
QUANT_DIM = 128


def bench_quant() -> dict:
    """Int8 post-training quantization (core/quantize.py): batch
    scoring throughput f32 vs int8 on (a) the serving-bench MLP
    TPUModel and (b) a fused StandardScaler->logistic pipeline, plus
    the accuracy cost (top-1 agreement, probability max-abs-err).

    HONESTY NOTE: the int8 win is an MXU-class claim — integer matmul
    doubles effective per-chip batch throughput where the hardware has
    an int8 systolic path. This container's CPU backend has no integer
    matmul advantage (XLA's CPU int8 dot is often SLOWER than its
    oneDNN f32 gemm), so the JSON records the measured ratio with the
    backend labeled instead of asserting a win the hardware can't
    show; the accuracy floors are backend-independent and pinned in
    tests/test_quantize.py."""
    import jax

    from mmlspark_tpu.core.stage import Pipeline
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.linear import TPULogisticRegression
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.stages.dataprep import StandardScaler

    rng = np.random.default_rng(0)
    n = QUANT_ROWS

    # (a) MLP TPUModel — the serving-bench scorer shape
    module = build_network({"type": "mlp", "features": [256, 128],
                            "num_classes": 10})
    x0 = np.zeros((1, QUANT_DIM), np.float32)
    model = TPUModel.from_flax(
        module, module.init(jax.random.PRNGKey(0), x0),
        inputCol="features", outputCol="scores", batchSize=1024)
    X = rng.normal(size=(n, QUANT_DIM)).astype(np.float32)
    calib = X[:2048]
    qmodel = model.quantize({"features": calib})
    table = DataTable({"features": X})

    def best(fn, reps=3):
        w, out = 1e18, None
        for _ in range(reps):
            t0 = time.time()
            out = fn()
            w = min(w, time.time() - t0)
        return w, out

    model.transform(DataTable({"features": X[:4096]}))   # warm compiles
    qmodel.transform(DataTable({"features": X[:4096]}))
    f32_s, out_f = best(lambda: model.transform(table))
    int8_s, out_q = best(lambda: qmodel.transform(table))
    sf = np.asarray(out_f["scores"])
    sq = np.asarray(out_q["scores"])
    mlp_agree = float((sf.argmax(-1) == sq.argmax(-1)).mean())

    # (b) fused pipeline — scaler + logistic, the PR 9 serving shape
    y = (X[:, 0] - 0.5 * X[:, 3] > 0).astype(np.float64)
    pt = DataTable({"features": X, "label": y})
    pm = Pipeline(stages=[
        StandardScaler(inputCol="features", outputCol="features"),
        TPULogisticRegression(featuresCol="features", labelCol="label",
                              maxIter=40),
    ]).fit(pt.slice(0, 50_000))
    fused = pm.fused(batch_size=1024)
    qfused = fused.quantize(pt.slice(0, 2048))
    fused.transform(pt.slice(0, 4096))
    qfused.transform(pt.slice(0, 4096))
    pf32_s, pout_f = best(lambda: fused.transform(pt))
    pint8_s, pout_q = best(lambda: qfused.transform(pt))
    pipe_agree = float(
        (np.asarray(pout_f["prediction"])
         == np.asarray(pout_q["prediction"])).mean())
    prob_err = float(np.abs(np.asarray(pout_f["probability"])
                            - np.asarray(pout_q["probability"])).max())

    return {
        "metric": "int8_vs_f32_batch_scoring",
        "value": round(f32_s / int8_s, 3) if int8_s else None,
        "unit": "x (f32 wall / int8 wall, MLP TPUModel; >1 = int8 "
                "faster — only expected where the backend has an "
                "integer matmul advantage)",
        "backend": jax.default_backend(),
        "mlp_f32_s": round(f32_s, 3),
        "mlp_int8_s": round(int8_s, 3),
        "mlp_top1_agreement": round(mlp_agree, 5),
        "pipeline_f32_s": round(pf32_s, 3),
        "pipeline_int8_s": round(pint8_s, 3),
        "pipeline_int8_speedup": round(pf32_s / pint8_s, 3)
        if pint8_s else None,
        "pipeline_pred_agreement": round(pipe_agree, 5),
        "pipeline_prob_max_abs_err": round(prob_err, 5),
        "config": (f"{n} rows x {QUANT_DIM} feats; MLP-256/128 "
                   f"TPUModel + fused scaler->logistic(40); "
                   f"per-channel weight scales, per-tensor activation "
                   f"clip on 2048 calib rows, int8xint8->i32 dot + "
                   f"f32 dequant epilogue"),
    }


# the cold-start subject: a compile-bound transformer classifier — the
# model class where trace-at-startup actually hurts (a small MLP's
# compile is noise next to the interpreter+jax import both modes pay)
COLDSTART_SPEC = {"type": "transformer", "vocab_size": 2000, "dim": 128,
                  "depth": 4, "heads": 4, "max_len": 64,
                  "num_classes": 8}
COLDSTART_REPS = 2


def _coldstart_export(art: str) -> None:
    """Child half of bench_coldstart: build the model and export the
    AOT artifact on the device, in a process of its own. Prints the
    manifest facts the parent reports."""
    import jax

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving import aot

    module = build_network(dict(COLDSTART_SPEC))
    x0 = np.zeros((1, COLDSTART_SPEC["max_len"]), np.int32)
    model = TPUModel.from_flax(
        module, module.init(jax.random.PRNGKey(0), x0),
        inputCol="features", outputCol="scores", batchSize=64)
    t0 = time.time()
    manifest = aot.export_model(model, {"features": x0}, art,
                                version="bench-v1")
    print(json.dumps({"format": manifest["format"],
                      "buckets": len(manifest["buckets"]),
                      "export_wall_s": round(time.time() - t0, 2)}))


def bench_coldstart() -> dict:
    """Replica cold-start (serving/aot.py): export one AOT artifact,
    then start FRESH serving-replica processes in both modes —
    ``trace`` (rebuild model, per-bucket trace+compile warmup: today's
    replica) and ``aot`` (deserialize pre-compiled executables, XLA
    cache seeded at export) — measuring process start -> first HTTP
    200 (``cold_start_to_first_200_ms``). Also proves the AOT replica
    never traces: jit_traces_total == 0 through load, warmup, and the
    request. Floor-pinned >= 3x in tests/test_perf_floors.py.

    Every step is a child process that needs the device, one at a
    time, so this process must not have touched jax: a chip belongs to
    one process (main() runs this scenario first)."""
    import subprocess
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))

    def child(argv) -> dict:
        proc = subprocess.run(
            [sys.executable] + argv, capture_output=True, text=True,
            cwd=here, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"coldstart runner failed: "
                               f"{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    art = tempfile.mkdtemp(prefix="mmlspark_aot_bench_")
    exported = child(
        ["-c", f"import bench; bench._coldstart_export({art!r})"])

    def run(mode: str, port: int) -> dict:
        return child(["-m", "mmlspark_tpu.serving.aot", art,
                      "--mode", mode, "--port", str(port)])

    best = {"trace": None, "aot": None}
    port = 19940
    for _ in range(COLDSTART_REPS):   # interleaved: noise hits both
        for mode in ("trace", "aot"):
            r = run(mode, port)
            port += 3
            if (best[mode] is None
                    or r["cold_start_to_first_200_ms"]
                    < best[mode]["cold_start_to_first_200_ms"]):
                best[mode] = r
    trace_ms = best["trace"]["cold_start_to_first_200_ms"]
    aot_ms = best["aot"]["cold_start_to_first_200_ms"]
    return {
        "metric": "cold_start_to_first_200_ms",
        "value": round(trace_ms / aot_ms, 2) if aot_ms else None,
        "unit": "x (trace-at-startup / AOT-loaded, fresh replica "
                "processes, best-of-interleaved reps)",
        "trace_ms": trace_ms,
        "aot_ms": aot_ms,
        "trace_detail": best["trace"],
        "aot_detail": best["aot"],
        "aot_zero_traces": best["aot"]["jit_traces_total"] == 0,
        "artifact_format": exported["format"],
        "export_wall_s": exported["export_wall_s"],
        "backend": best["aot"]["backend"],
        "config": (f"transformer dim {COLDSTART_SPEC['dim']} depth "
                   f"{COLDSTART_SPEC['depth']} seq "
                   f"{COLDSTART_SPEC['max_len']}, "
                   f"{exported['buckets']} buckets, "
                   f"{COLDSTART_REPS} reps/mode"),
    }


ZOO_MODELS = 256
ZOO_MAX_RESIDENT = 32
ZOO_REQUESTS = 2000
ZOO_CLIENTS = 16


def bench_zoo() -> dict:
    """The multi-model serving plane (serving/zoo.py): ZOO_MODELS
    distinct versioned models behind one 2-engine fleet, mixed-tenant
    load over a skewed model distribution with only ZOO_MAX_RESIDENT
    resident at once — so the run measures p99 UNDER CHURN (activations
    and LRU evictions happening mid-traffic), availability, and the
    cold-model activation wall through the AOT load path (export one
    real artifact, activate it cold, report the audit event's ms)."""
    import concurrent.futures
    import tempfile
    import threading
    import urllib.error

    import jax

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving import (
        AdmissionController, ModelZoo, ServingFleet,
        ServingUnavailable, aot,
    )
    from mmlspark_tpu.stages.basic import Lambda

    rng = np.random.default_rng(0)

    def scoring_stage(tag, w):
        # a real (host numpy) per-model compute so batches cost
        # something; distinct weights per model
        def handle(table):
            feats = np.asarray(
                [json.loads(r["entity"].decode())["features"]
                 for r in table["request"]], np.float32)
            scores = feats @ w
            return table.with_column("reply", [
                {"model": tag, "prediction": int(s.argmax())}
                for s in scores])
        return Lambda.apply(handle)

    zoo = ModelZoo(max_resident=ZOO_MAX_RESIDENT, memory_probe=None)
    dim, classes = 16, 8
    for i in range(ZOO_MODELS):
        w = rng.normal(size=(dim, classes)).astype(np.float32)
        zoo.register_factory(
            f"m{i:03d}", f"v{i % 8}",
            (lambda i=i, w=w: scoring_stage(f"m{i:03d}", w)),
            metadata={"cost_bytes": int(w.nbytes)})

    # ONE real AOT artifact: the cold-activation-in-hundreds-of-ms
    # claim is measured on the genuine load path, not a factory
    module = build_network({"type": "mlp", "features": [64, 32],
                            "num_classes": classes})
    x0 = np.zeros((1, dim), np.float32)
    tpu_model = TPUModel.from_flax(
        module, module.init(jax.random.PRNGKey(0), x0),
        inputCol="features", outputCol="scores", batchSize=64)
    art = tempfile.mkdtemp(prefix="mmlspark_zoo_bench_")
    aot.export_model(tpu_model, {"features": x0}, art, version="v1")
    zoo.register_artifact("aot_scorer", "v1", art)

    admission = AdmissionController()   # default tiers, no quotas
    fleet = ServingFleet(n_engines=2, base_port=19860, batch_size=64,
                         workers=2, max_wait_ms=3.0, zoo=zoo,
                         admission=admission, tracing=False)
    # skewed popularity (zipf-ish): a hot head keeps the cache busy
    # while a long tail forces continuous activations + evictions
    ranks = np.arange(1, ZOO_MODELS + 1, dtype=np.float64)
    probs = (1.0 / ranks ** 1.1)
    probs /= probs.sum()
    picks = rng.choice(ZOO_MODELS, size=ZOO_REQUESTS, p=probs)
    payload = json.dumps(
        {"features": rng.normal(size=dim).tolist()}).encode()
    lock = threading.Lock()
    lat, failures = [], []

    def post(i):
        model = f"m{picks[i]:03d}"
        tenant = f"t{i % 4}"
        t0 = time.perf_counter()
        try:
            body = fleet.post(payload, model=model, tenant=tenant,
                              timeout=120)
            assert body["model"] == model, (model, body)   # no mixing
            ok = True
        except urllib.error.HTTPError as e:
            with lock:
                failures.append(e.code)
            ok = False
        except ServingUnavailable:
            # fleet-level unavailability (both circuits open) is a
            # FAILED request in the availability metric, not a
            # crashed bench
            with lock:
                failures.append(503)
            ok = False
        dt = (time.perf_counter() - t0) * 1e3
        with lock:
            lat.append(dt)
        return ok

    try:
        # cold AOT activation measured through live HTTP: first
        # request to the never-loaded artifact model
        t0 = time.perf_counter()
        body = fleet.post(payload, model="aot_scorer", timeout=300)
        aot_first_request_ms = (time.perf_counter() - t0) * 1e3
        assert "prediction" in body
        activate_ev = [e for e in zoo.events if e.kind == "activate"
                       and e.model == "aot_scorer"][0]
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(ZOO_CLIENTS) as ex:
            results = list(ex.map(post, range(ZOO_REQUESTS)))
        wall = time.perf_counter() - t0
        stats = zoo.stats()
        distinct_served = len({f"m{p:03d}" for p in picks})
    finally:
        fleet.stop_all()
        zoo.close()
    lat_arr = np.asarray(sorted(lat))
    availability = sum(results) / len(results)
    return {
        "metric": "zoo_p99_ms_under_churn",
        "value": round(float(np.percentile(lat_arr, 99)), 1),
        "unit": "ms",
        "models_registered": ZOO_MODELS + 1,
        "distinct_models_requested": distinct_served,
        "max_resident": ZOO_MAX_RESIDENT,
        "qps": round(ZOO_REQUESTS / wall, 1),
        "p50_ms": round(float(np.percentile(lat_arr, 50)), 1),
        "availability": round(availability, 4),
        "failure_codes": sorted(set(failures)),
        "activations": stats["activations"],
        "evictions": stats["evictions"],
        "evictions_with_outstanding":
            stats["evictions_with_outstanding"],
        "cold_aot_activation_ms": round(activate_ev.stats["ms"], 1),
        "cold_aot_first_request_ms": round(aot_first_request_ms, 1),
        "backend": jax.default_backend(),
        "config": (f"{ZOO_MODELS} factory models + 1 AOT artifact, "
                   f"cache {ZOO_MAX_RESIDENT}, zipf(1.1) picks, "
                   f"{ZOO_REQUESTS} reqs x {ZOO_CLIENTS} clients, "
                   f"4 tenants, 2 engines x 2 workers"),
    }


# sharded serving bench (docs/sharded_serving.md): a Transformer
# classifier big enough that 8-way tensor sharding visibly splits the
# weights, served tensor-parallel over the virtual mesh
SHARDED_SPEC = {"type": "transformer", "vocab_size": 8192, "dim": 256,
                "depth": 2, "heads": 8, "max_len": 64,
                "num_classes": 16}
SHARDED_MESH_DEVICES = 8


def bench_sharded() -> dict:
    """Mesh-sharded serving (serving/sharded.py): a Transformer whose
    weights shard 8-way across the (virtual) mesh — per-device
    residency evidence for the too-big-for-one-device example, parity
    vs the unsharded oracle, zero steady-state recompiles, and the
    sharded AOT artifact's fresh-process cold-start ratio (trace-mode
    sharded startup vs AOT-loaded sharded startup)."""
    import subprocess
    import sys
    import tempfile

    import jax

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving import aot, sharded as SH
    from mmlspark_tpu.utils.jax_compat import set_cpu_device_count

    if jax.default_backend() != "cpu":
        # its cold-start children need the same mesh this process
        # holds; on chips that is two processes on one device
        raise RuntimeError(
            f"the sharded scenario runs on a virtual "
            f"{SHARDED_MESH_DEVICES}-device CPU mesh; run it with "
            f"JAX_PLATFORMS=cpu")
    if len(jax.devices()) < SHARDED_MESH_DEVICES:
        # forcing virtual CPU devices only works BEFORE first backend
        # use — by the time a scenario runs, main() has initialized
        # the backend, so the pre-init in main() (gated on
        # JAX_PLATFORMS=cpu) is the only working path. A late
        # set_cpu_device_count here would silently no-op; fail with
        # the recipe instead.
        set_cpu_device_count(SHARDED_MESH_DEVICES)
        if len(jax.devices()) < SHARDED_MESH_DEVICES:
            raise RuntimeError(
                "sharded scenario needs a virtual "
                f"{SHARDED_MESH_DEVICES}-device mesh but the backend "
                "already initialized with "
                f"{len(jax.devices())} device(s); run with "
                "JAX_PLATFORMS=cpu (bench pre-forces the device count "
                "before backend init) or export XLA_FLAGS="
                f"--xla_force_host_platform_device_count="
                f"{SHARDED_MESH_DEVICES}")
    module = build_network(dict(SHARDED_SPEC))
    rng = np.random.default_rng(0)
    batch = 64
    toks = rng.integers(0, SHARDED_SPEC["vocab_size"],
                        size=(batch, 32)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(0), toks[:1])
    oracle = TPUModel.from_flax(module, variables, inputCol="tokens",
                                outputCol="scores", batchSize=batch)
    model = TPUModel.from_flax(module, variables, inputCol="tokens",
                               outputCol="scores", batchSize=batch)
    mesh = SH.serving_mesh({"model": SHARDED_MESH_DEVICES})
    SH.tensor_shard_model(model, mesh)

    table = DataTable({"tokens": toks})
    ref = np.asarray(oracle.transform(table)["scores"])
    out = np.asarray(model.transform(table)["scores"])
    parity = float(np.abs(ref - out).max())

    res = SH.device_residency(model)
    # raises if any single device holds the full weight set — the
    # same assertion the tests pin; returns (max/device, total)
    _, total_logical = SH.assert_serves_from_mesh(model)

    # steady-state sharded batch latency (+ the recompile guard)
    for _ in range(2):
        model.transform(table)
    misses = model.jit_cache_misses
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        model.transform(table)
    sharded_ms = (time.perf_counter() - t0) / reps * 1e3
    recompiles = model.jit_cache_misses - misses
    t0 = time.perf_counter()
    for _ in range(reps):
        oracle.transform(table)
    oracle_ms = (time.perf_counter() - t0) / reps * 1e3

    # sharded AOT artifact: fresh-process cold start, trace vs aot
    art = tempfile.mkdtemp(prefix="mmlspark_sharded_aot_")
    t0 = time.time()
    manifest = aot.export_model(model, {"tokens": toks[:2]}, art,
                                version="bench-v1")
    export_s = time.time() - t0

    def run(mode: str, port: int) -> dict:
        proc = subprocess.run(
            [sys.executable, "-m", "mmlspark_tpu.serving.aot", art,
             "--mode", mode, "--port", str(port)],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=600,
            env=_cpu_child_env())
        if proc.returncode != 0:
            raise RuntimeError(f"sharded coldstart runner failed: "
                               f"{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    best = {"trace": None, "aot": None}
    port = 19840
    for _ in range(2):                # interleaved: noise hits both
        for mode in ("trace", "aot"):
            r = run(mode, port)
            port += 3
            if (best[mode] is None
                    or r["cold_start_to_first_200_ms"]
                    < best[mode]["cold_start_to_first_200_ms"]):
                best[mode] = r
    trace_ms = best["trace"]["cold_start_to_first_200_ms"]
    aot_ms = best["aot"]["cold_start_to_first_200_ms"]

    per_dev = res["per_device_bytes"]
    return {
        "metric": "sharded_coldstart_trace_over_aot",
        "value": round(trace_ms / aot_ms, 2) if aot_ms else None,
        "unit": "x (traced sharded startup / sharded-AOT startup, "
                "fresh replica processes, best-of-2 interleaved)",
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "parity_max_abs_err_vs_unsharded": parity,
        "weights_total_bytes": total_logical,
        "max_device_bytes": res["max_device_bytes"],
        "max_device_fraction_of_total": round(
            res["max_device_bytes"] / total_logical, 4),
        "per_device_bytes": {k: int(v) for k, v in
                             sorted(per_dev.items())},
        "fits_one_device": res["max_device_bytes"] >= total_logical,
        "steady_state_recompiles": int(recompiles),
        "sharded_batch_ms": round(sharded_ms, 1),
        "single_device_batch_ms": round(oracle_ms, 1),
        "coldstart_trace_ms": trace_ms,
        "coldstart_aot_ms": aot_ms,
        "aot_zero_traces": best["aot"]["jit_traces_total"] == 0,
        "artifact_format": manifest["format"],
        "export_wall_s": round(export_s, 2),
        "backend": jax.default_backend(),
        "config": (f"transformer dim {SHARDED_SPEC['dim']} depth "
                   f"{SHARDED_SPEC['depth']} vocab "
                   f"{SHARDED_SPEC['vocab_size']}, batch {batch}, "
                   f"{SHARDED_MESH_DEVICES}-way tensor sharding; NOTE "
                   f"8 VIRTUAL devices timeshare this host's CPU — "
                   f"the latency comparison measures overhead, the "
                   f"residency/parity/cold-start numbers are the "
                   f"point"),
    }


OOC_ROWS = 10_000_000
OOC_CHUNK = 262_144
OOC_BUDGET_BYTES = 1_500_000_000    # 1.5 GB host budget for the
#                                     streamed pass (RSS growth AND
#                                     tracked bytes) — the materialized
#                                     path provably exceeds it
OOC_PREFETCH = 3


def bench_ooc() -> dict:
    """Out-of-core ingest (io/ooc.py + gbdt/sketch.py): a 10M-row
    Featurize -> StandardScaler -> logistic scoring pass streamed
    chunk-at-a-time through the fused pipeline under an ENFORCED host
    memory budget — asserted from both peak-RSS growth and tracked
    bytes — against the fully-materialized baseline (which provably
    exceeds the budget); ingest/compute overlap fraction from the
    ooc phase histograms; mergeable-sketch bin boundaries vs the exact
    one-shot fit (rank drift + the measured certificate) on a
    HIGGS-shaped 1M x 28 block; and a sketch-binned chunked GBDT train
    vs the reservoir-sample path."""
    import gc

    from mmlspark_tpu.automl.featurize import Featurize
    from mmlspark_tpu.core import metrics as MC
    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.stage import PipelineModel
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.gbdt.binning import BinMapper
    from mmlspark_tpu.io.ooc import (
        ChunkedTable, current_rss_bytes, peak_rss_bytes, table_nbytes,
    )
    from mmlspark_tpu.models.linear import TPULogisticRegression
    from mmlspark_tpu.stages.dataprep import StandardScaler

    levels = np.asarray([f"l{i}" for i in range(8)])
    vocab = np.asarray([f"w{i:02d}" for i in range(64)])

    def make_chunk(i: int, rows: int) -> DataTable:
        rng = np.random.default_rng(1000 + i)
        a = rng.normal(size=rows).astype(np.float32)
        b = np.where(rng.random(rows) < 0.1, np.nan,
                     rng.normal(size=rows)).astype(np.float32)
        cat = levels[rng.integers(0, len(levels), rows)].tolist()
        toks = vocab[rng.integers(0, len(vocab),
                                  size=(rows, 3))].tolist()
        return DataTable({"a": a, "b": b, "cat": cat, "toks": toks})

    def factory():
        done, i = 0, 0
        while done < OOC_ROWS:
            rows = min(OOC_CHUNK, OOC_ROWS - done)
            yield make_chunk(i, rows)
            done += rows
            i += 1

    def fresh_source(depth: int = OOC_PREFETCH) -> ChunkedTable:
        return ChunkedTable.from_generator(factory, num_rows=OOC_ROWS,
                                           prefetch_depth=depth)

    # -- fit: streaming Featurize + scaler + a sample-fitted model ------
    print("# ooc: streaming featurize fit ...", flush=True)
    t0 = time.perf_counter()
    fz_model = Featurize(featureColumns=["a", "b", "cat", "toks"],
                         numberOfFeatures=32).fit(fresh_source())
    fit_wall = time.perf_counter() - t0
    sample = DataTable.concat([make_chunk(0, OOC_CHUNK),
                               make_chunk(1, OOC_CHUNK)])
    feat_sample = fz_model.transform(sample)
    scaler = StandardScaler(inputCol="features").fit(
        ChunkedTable.from_table(feat_sample, chunk_rows=OOC_CHUNK))
    scaled = scaler.transform(feat_sample)
    rng = np.random.default_rng(0)
    a_col = np.asarray(sample["a"], np.float64)
    y = (a_col + rng.normal(scale=0.5, size=len(a_col)) > 0).astype(
        np.float64)
    logit = TPULogisticRegression(
        featuresCol="features", labelCol="label", maxIter=10).fit(
        scaled.with_column("label", y))
    fused = fuse([fz_model, scaler, logit], batch_size=OOC_CHUNK)

    # -- streamed pass under the budget --------------------------------
    print("# ooc: streamed scoring pass ...", flush=True)
    for h in MC.ooc_histograms().values():
        h.reset()
    gc.collect()
    src = fresh_source()
    rss_before = current_rss_bytes()
    peak_before = peak_rss_bytes()
    t0 = time.perf_counter()
    rows = 0
    pred_sum = 0.0
    first_chunk_pred = None
    for out in fused.transform_chunked(src):
        p = np.asarray(out["prediction"])
        if first_chunk_pred is None:
            first_chunk_pred = p.copy()
        rows += len(p)
        pred_sum += float(p.sum())
    streamed_wall = time.perf_counter() - t0
    assert rows == OOC_ROWS
    streamed_rss_growth = max(peak_rss_bytes(), peak_before) - rss_before
    streamed_tracked = src.stats.tracked_peak_bytes()
    phases = {k: h.snapshot() for k, h in MC.ooc_histograms().items()}
    worker_s = (phases["decode"]["sum"] + phases["prepare"]["sum"]) / 1e3
    consumer_s = phases["dispatch"]["sum"] / 1e3
    wait_s = phases["wait"]["sum"] / 1e3
    overlap = 0.0
    if min(worker_s, consumer_s) > 0:
        overlap = max(0.0, min(1.0, (worker_s + consumer_s
                                     - streamed_wall)
                               / min(worker_s, consumer_s)))
    # the 1-core-visible pipelining signal: what fraction of the decode
    # wall the consumer did NOT block for (the prefetcher ran decode
    # while the consumer was busy — time-sliced here, truly parallel on
    # a multi-core/TPU host where `overlap` itself becomes nonzero)
    decode_hidden = 0.0
    if phases["decode"]["sum"] > 0:
        decode_hidden = max(0.0, min(1.0, 1.0 - phases["wait"]["sum"]
                                     / phases["decode"]["sum"]))

    # the budget holds on BOTH trackers, or the scenario fails loudly
    assert streamed_tracked < OOC_BUDGET_BYTES, (
        f"streamed tracked bytes {streamed_tracked} over budget")
    assert streamed_rss_growth < OOC_BUDGET_BYTES, (
        f"streamed RSS growth {streamed_rss_growth} over budget")

    # -- materialized baseline (provably over the budget) --------------
    print("# ooc: materialized baseline ...", flush=True)
    gc.collect()
    rss_mat0 = current_rss_bytes()
    t0 = time.perf_counter()
    mat = fresh_source(depth=0).materialize()
    feats_mat = fused.transform(mat)
    mat_wall = time.perf_counter() - t0
    mat_pred = np.asarray(feats_mat["prediction"])
    mat_rss_growth = peak_rss_bytes() - rss_mat0
    mat_tracked = table_nbytes(mat) + table_nbytes(feats_mat)
    assert np.array_equal(first_chunk_pred, mat_pred[:OOC_CHUNK]), \
        "streamed scoring diverged from the materialized oracle"
    assert mat_tracked > OOC_BUDGET_BYTES, (
        f"materialized path unexpectedly fit the budget: {mat_tracked}")
    assert mat_rss_growth > OOC_BUDGET_BYTES, (
        f"materialized RSS growth under budget: {mat_rss_growth}")
    pred_match = bool(abs(mat_pred.sum() - pred_sum) < 1e-6 * OOC_ROWS)
    del mat, feats_mat, mat_pred
    gc.collect()

    # -- sketch-vs-exact bin boundaries (HIGGS-shaped 1M x 28) ----------
    print("# ooc: sketch-vs-exact boundaries ...", flush=True)
    hn, hf = 1_000_000, 28
    hrng = np.random.default_rng(7)
    H = hrng.normal(size=(hn, hf)).astype(np.float32)
    h_chunks = [H[i:i + OOC_CHUNK] for i in range(0, hn, OOC_CHUNK)]
    t0 = time.perf_counter()
    m_sketch = BinMapper.fit_streaming(iter(h_chunks), max_bin=255)
    sketch_fit_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    m_exact = BinMapper.fit(H, max_bin=255, sample_cnt=hn)
    exact_fit_wall = time.perf_counter() - t0
    drift = 0.0
    for j in range(hf):
        xs = np.sort(H[:, j].astype(np.float64))
        ca, cb = m_sketch.upper_bounds[j], m_exact.upper_bounds[j]
        k = min(len(ca), len(cb))
        ra = np.searchsorted(xs, ca[:k], side="left") / hn
        rb = np.searchsorted(xs, cb[:k], side="left") / hn
        drift = max(drift, float(np.max(np.abs(ra - rb))))
    assert drift <= 2 * m_sketch.sketch_eps + 2.0 / 255, (
        f"cut drift {drift} exceeds the certificate bound")

    # -- chunked sketch-binned GBDT vs the reservoir-sample path --------
    # (HIGGS-shaped but shortened: this 1-core container pays ~15s per
    # boosting iteration at 1M rows — the full-length wall lives in the
    # higgs scenario; here the comparison is the BINNING path)
    from mmlspark_tpu.gbdt.booster import train
    gn = min(hn, 400_000)
    hy = (H[:gn, 0] + 0.6 * H[:gn, 1] * H[:gn, 2]
          + hrng.normal(scale=0.7, size=gn) > 0).astype(np.float64)

    def gbdt_factory():
        for i in range(0, gn, OOC_CHUNK):
            yield H[i:min(i + OOC_CHUNK, gn)], hy[i:i + OOC_CHUNK]

    gbdt = {"rows": gn, "iterations": 8}
    for mode in ("sketch", "sample"):
        print(f"# ooc: gbdt bin_fit={mode} ...", flush=True)
        params = {"objective": "binary", "num_iterations": 8,
                  "num_leaves": 63, "max_bin": 63, "seed": 0,
                  "bin_fit": mode}
        t0 = time.perf_counter()
        booster = train(params, gbdt_factory, y=None)
        wall = time.perf_counter() - t0
        p = booster.predict(H[:200_000])
        ys = hy[:200_000]
        order = np.argsort(p)
        ranks = np.empty(len(p))
        ranks[order] = np.arange(len(p))
        pos = ys == 1
        auc = ((ranks[pos].sum() - pos.sum() * (pos.sum() - 1) / 2)
               / (pos.sum() * (len(p) - pos.sum())))
        gbdt[mode] = {"train_wall_s": round(wall, 2),
                      "holdout_auc": round(float(auc), 4)}

    import jax
    return {
        "metric": "ooc_streamed_10m_featurize_model",
        "backend": jax.default_backend(),
        "rows": OOC_ROWS,
        "chunk_rows": OOC_CHUNK,
        "prefetch_depth": OOC_PREFETCH,
        "budget_bytes": OOC_BUDGET_BYTES,
        "featurize_fit_streaming_wall_s": round(fit_wall, 2),
        "streamed": {
            "wall_s": round(streamed_wall, 2),
            "rss_growth_bytes": int(streamed_rss_growth),
            "tracked_peak_bytes": int(streamed_tracked),
            "under_budget": True,
            "phase_s": {"decode": round(phases["decode"]["sum"] / 1e3, 2),
                        "prepare": round(
                            phases["prepare"]["sum"] / 1e3, 2),
                        "dispatch": round(consumer_s, 2),
                        "wait": round(wait_s, 2)},
            "ingest_compute_overlap_fraction": round(overlap, 3),
            "decode_hidden_fraction": round(decode_hidden, 3),
        },
        "materialized": {
            "wall_s": round(mat_wall, 2),
            "rss_growth_bytes": int(mat_rss_growth),
            "tracked_bytes": int(mat_tracked),
            "over_budget": True,
            "prediction_sum_matches": pred_match,
        },
        "streamed_vs_materialized_wall": round(
            mat_wall / max(streamed_wall, 1e-9), 3),
        "sketch_binning_1m_x28": {
            "sketch_eps_certificate": round(m_sketch.sketch_eps, 6),
            "max_cut_rank_drift_vs_exact": round(drift, 6),
            "bound_2eps": round(2 * m_sketch.sketch_eps, 6),
            "fit_streaming_wall_s": round(sketch_fit_wall, 2),
            "fit_exact_wall_s": round(exact_fit_wall, 2),
            "f32_cuts_exact": bool(m_sketch.f32_cuts_exact),
        },
        "gbdt_chunked_1m_x28_8iter": gbdt,
        "notes": ("CPU container, single usable core: overlap is "
                  "bounded by the decode thread and XLA's compute "
                  "threads timesharing one core — the phase sums and "
                  "the budget assertions are the point; a TPU host "
                  "overlaps host decode with device compute for real"),
    }


FLEET_PROCS = 4
FLEET_LOAD_S = 10.0
FLEET_CLIENTS = 16
FLEET_ROWS_PER_REQ = 64


def bench_fleet_procs() -> dict:
    """The REAL multi-process fleet: N serving engines as OS processes
    (tests/serving_worker.py --scorer linear) behind
    ``ServingFleet.connect`` with the startup probe, driven by a
    columnar load generator (``post_columns`` — msgpack record
    batches); throughput scaling vs ONE process, plus the chaos drill
    (SIGKILL one engine mid-load, availability floor). Replaces the
    threads-in-one-process fleet numbers for the multi-process story."""
    import signal as _signal
    import subprocess
    import sys
    import threading

    import jax

    from mmlspark_tpu.serving.fleet import ServingFleet

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "serving_worker.py")
    dim = 16
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(FLEET_ROWS_PER_REQ, dim)).astype(np.float32)

    def spawn(n):
        procs, addrs = [], []
        for wid in range(n):
            import socket
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            p = subprocess.Popen(
                [sys.executable, worker, str(port), str(wid),
                 "--scorer", "linear", "--dim", str(dim),
                 "--batch-size", "64", "--workers", "1"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=_cpu_child_env())
            procs.append(p)
        for p in procs:
            line = p.stdout.readline().strip()
            addrs.append(line.split()[2])
        return procs, addrs

    def drive(fleet, duration_s, kill=None, procs=None):
        """Closed-loop columnar load; optionally SIGKILL one worker
        mid-window. Returns (rows_ok, requests_ok, failed, wall_s)."""
        stats = {"ok": 0, "failed": 0}
        lock = threading.Lock()
        stop = threading.Event()

        def client():
            while not stop.is_set():
                try:
                    rep = fleet.post_columns({"features": rows},
                                             timeout=30)
                    n = len(rep["prediction"])
                    with lock:
                        stats["ok"] += n
                except Exception:  # noqa: BLE001
                    with lock:
                        stats["failed"] += 1

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(FLEET_CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        if kill is not None:
            time.sleep(duration_s * 0.4)
            procs[kill].send_signal(_signal.SIGKILL)
            time.sleep(duration_s * 0.6)
        else:
            time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        wall = time.perf_counter() - t0
        reqs_ok = stats["ok"] // FLEET_ROWS_PER_REQ
        return stats["ok"], reqs_ok, stats["failed"], wall

    out = {}
    for n in (1, FLEET_PROCS):
        procs, addrs = spawn(n)
        try:
            fleet = ServingFleet.connect(addrs, wait_ready_s=120.0,
                                         tracing=False)
            drive(fleet, 1.5)                      # warm connections
            rows_ok, reqs, failed, wall = drive(fleet, FLEET_LOAD_S)
            out[n] = {"rows_per_s": round(rows_ok / wall, 1),
                      "requests_ok": reqs, "failed": failed}
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait(timeout=30)

    # chaos: fresh N-process fleet, SIGKILL one engine mid-load
    procs, addrs = spawn(FLEET_PROCS)
    try:
        fleet = ServingFleet.connect(addrs, wait_ready_s=120.0,
                                     failure_threshold=2,
                                     breaker_cooldown=1.0,
                                     tracing=False)
        drive(fleet, 1.5)
        rows_ok, reqs, failed, wall = drive(
            fleet, FLEET_LOAD_S, kill=0, procs=procs)
        availability = reqs / max(1, reqs + failed)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)

    usable_cores = len(os.sched_getaffinity(0))
    scaling = (out[FLEET_PROCS]["rows_per_s"]
               / max(1e-9, out[1]["rows_per_s"]))
    return {
        "metric": "fleet_procs_throughput_scaling",
        "value": round(scaling, 2),
        "unit": f"x ({FLEET_PROCS} engine processes vs 1, columnar "
                f"load generator)",
        "one_proc": out[1],
        "n_procs": out[FLEET_PROCS],
        "engine_processes": FLEET_PROCS,
        "clients": FLEET_CLIENTS,
        "rows_per_request": FLEET_ROWS_PER_REQ,
        "chaos_kill_one": {
            "availability": round(availability, 4),
            "requests_ok": reqs, "failed": failed,
            "rows_per_s": round(rows_ok / wall, 1),
        },
        "usable_cores": usable_cores,
        "scaling_note": (
            "process scaling is bounded by usable cores: the >=2.5x "
            "floor is a multi-core claim (tests/test_sharded.py gates "
            "it on >=4 cores), this container exposes "
            f"{usable_cores}"),
        "backend": jax.default_backend(),
    }


FABRIC_PROCS = 4
FABRIC_LOAD_S = 6.0
FABRIC_CLIENTS = 4
FABRIC_ROWS_PER_REQ = 512


def bench_fabric() -> dict:
    """The multi-host fabric (PR 17): (1) co-located shared-memory
    columnar transport vs HTTP+msgpack over the SAME 4-process fleet —
    rows/s and request p50/p99 at equal availability; (2) the
    placement-plane churn drill — a hot model earns replicas, demand
    flips mid-window, rebuild latency and assignment-event counts from
    the controller's own histogram; (3) a REAL 2-process
    ``jax.distributed`` group (tests/multihost_worker.py) running the
    sketch-binned multi-host GBDT fit, wall clock from spawn to OK with
    the bit-identical forest digest asserted across members."""
    import signal as _signal  # noqa: F401  (parity with fleet bench)
    import subprocess
    import sys
    import threading

    import jax

    from mmlspark_tpu.core.metrics import LatencyHistogram
    from mmlspark_tpu.serving.fleet import ServingFleet

    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests")
    worker = os.path.join(tests_dir, "serving_worker.py")
    dim = 16
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(FABRIC_ROWS_PER_REQ, dim)).astype(np.float32)

    def _free_port():
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(n):
        procs, addrs = [], []
        for wid in range(n):
            port = _free_port()
            p = subprocess.Popen(
                [sys.executable, worker, str(port), str(wid),
                 "--scorer", "linear", "--dim", str(dim),
                 "--batch-size", "64", "--workers", "1"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=_cpu_child_env())
            procs.append(p)
        for p in procs:
            line = p.stdout.readline().strip()
            addrs.append(line.split()[2])
        return procs, addrs

    def drive(fleet, duration_s):
        """Closed-loop columnar load with per-request latency capture.
        Returns (rows_ok, requests_ok, failed, wall_s, hist)."""
        stats = {"ok": 0, "failed": 0}
        hist = LatencyHistogram(unit="ms")
        lock = threading.Lock()
        stop = threading.Event()

        def client():
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    rep = fleet.post_columns({"features": rows},
                                             timeout=30)
                    ms = (time.perf_counter() - t0) * 1e3
                    n = len(rep["prediction"])
                    with lock:
                        stats["ok"] += n
                        hist.observe(ms)
                except Exception:  # noqa: BLE001
                    with lock:
                        stats["failed"] += 1

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(FABRIC_CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        wall = time.perf_counter() - t0
        reqs_ok = stats["ok"] // FABRIC_ROWS_PER_REQ
        return stats["ok"], reqs_ok, stats["failed"], wall, hist

    # --- (1) shm vs HTTP+msgpack over the SAME worker processes ---
    transports = {}
    procs, addrs = spawn(FABRIC_PROCS)
    try:
        for label, use_shm in (("shm", True), ("http_msgpack", False)):
            fleet = ServingFleet.connect(addrs, wait_ready_s=120.0,
                                         tracing=False,
                                         shm_transport=use_shm)
            try:
                drive(fleet, 1.5)                  # warm + negotiate
                rows_ok, reqs, failed, wall, hist = drive(
                    fleet, FABRIC_LOAD_S)
                entry = {
                    "rows_per_s": round(rows_ok / wall, 1),
                    "requests_ok": reqs, "failed": failed,
                    "p50_ms": round(hist.percentile(50), 2),
                    "p99_ms": round(hist.percentile(99), 2),
                    "availability": round(
                        reqs / max(1, reqs + failed), 4),
                }
                if use_shm:
                    from mmlspark_tpu.io import shm as shm_mod
                    s = shm_mod.stats()
                    entry["negotiated"] = bool(fleet._shm_ok)
                    entry["fallbacks"] = fleet._shm_fallbacks
                    entry["shm_batches"] = s.get("batches", 0)
                    entry["shm_bytes"] = s.get("bytes", 0)
                    entry["gen_mismatch"] = s.get("gen_mismatch", 0)
            finally:
                # close the ring but leave the shared workers alive
                # for the second transport's run
                fleet.close_shm()
            transports[label] = entry
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)

    shm_vs_http = (transports["shm"]["rows_per_s"]
                   / max(1e-9, transports["http_msgpack"]["rows_per_s"]))

    # --- (2) placement-plane churn drill (in-process 2-engine fleet
    # sharing ONE zoo, demand flip mid-window) ---
    from mmlspark_tpu.serving.placement import PlacementEvent
    from mmlspark_tpu.serving.zoo import ModelZoo
    from mmlspark_tpu.stages.basic import Lambda

    def _echo(tag):
        def handle(table):
            replies = []
            for r in table["request"]:
                replies.append({"served_by": tag})
            return table.with_column("reply", replies)
        return Lambda.apply(handle)

    zoo = ModelZoo(memory_probe=None)
    for i in range(4):
        zoo.register_factory(f"m{i}", "v1",
                             (lambda i=i: _echo(f"m{i}")))
    pfleet = ServingFleet(n_engines=2, base_port=21510, zoo=zoo,
                          tracing=False)
    ctl = pfleet.attach_placement(rebuild_min_interval_s=0.0)
    churn = {}
    try:
        ok = failed = 0
        t0 = time.perf_counter()
        # phase A: m0 hot, m1..m3 cold
        for i in range(30):
            model = "m0" if i % 5 else f"m{1 + (i // 5) % 3}"
            try:
                pfleet.post({"x": i}, model=model)
                ok += 1
            except Exception:  # noqa: BLE001
                failed += 1
        ctl.rebuild(force=True)
        replicas_a = dict(ctl.replica_counts())
        # phase B: demand flips to m2 (hot enough to cross hot_share
        # against phase A's still-windowed m0 demand)
        for i in range(40):
            model = "m2"
            try:
                pfleet.post({"x": i}, model=model)
                ok += 1
            except Exception:  # noqa: BLE001
                failed += 1
        ctl.rebuild(force=True)
        replicas_b = dict(ctl.replica_counts())
        churn_wall = time.perf_counter() - t0
        st = ctl.stats()
        kinds = {}
        for ev in zoo.events:
            if isinstance(ev, PlacementEvent):
                kinds[ev.kind] = kinds.get(ev.kind, 0) + 1
        churn = {
            "hot_replicas_phase_a": replicas_a,
            "hot_replicas_phase_b": replicas_b,
            "rebuilds": st["rebuilds"],
            "stale_routes": st["stale_routes"],
            "placement_events": kinds,
            "rebuild_p50_ms": round(ctl.rebuild_hist.percentile(50), 3),
            "rebuild_p99_ms": round(ctl.rebuild_hist.percentile(99), 3),
            "availability": round(ok / max(1, ok + failed), 4),
            "wall_s": round(churn_wall, 2),
        }
    finally:
        pfleet.stop_all()
        zoo.close()

    # --- (3) 2-process jax.distributed sketch-GBDT fit wall ---
    mh_worker = os.path.join(tests_dir, "multihost_worker.py")
    env = {**{k: v for k, v in os.environ.items()
              if not k.startswith(("JAX_", "XLA_"))},
           "JAX_PLATFORMS": "cpu"}
    port = _free_port()
    t0 = time.perf_counter()
    mh_procs = [subprocess.Popen(
        [sys.executable, mh_worker, str(port), str(pid), "2"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for pid in range(2)]
    digests, mh_rcs = {}, []
    try:
        for p in mh_procs:
            out_txt, _err = p.communicate(timeout=300)
            mh_rcs.append(p.returncode)
            for line in out_txt.splitlines():
                if line.startswith("DIGEST"):
                    _, pid, digest, _bdig, _acc = line.split()
                    digests[int(pid)] = digest
    except subprocess.TimeoutExpired:
        for p in mh_procs:
            p.kill()
    group_wall = time.perf_counter() - t0
    group = {
        "wall_s": round(group_wall, 2),
        "rcs": mh_rcs,
        "forest_digest": digests.get(0),
        "bit_identical": (len(digests) == 2
                          and len(set(digests.values())) == 1),
    }

    usable_cores = len(os.sched_getaffinity(0))
    return {
        "metric": "fabric_shm_vs_http_rows_per_s",
        "value": round(shm_vs_http, 2),
        "unit": f"x (shm columnar vs HTTP+msgpack, {FABRIC_PROCS} "
                f"engine processes, {FABRIC_ROWS_PER_REQ} rows/req)",
        "transports": transports,
        "placement_churn": churn,
        "process_group_gbdt": group,
        "engine_processes": FABRIC_PROCS,
        "clients": FABRIC_CLIENTS,
        "rows_per_request": FABRIC_ROWS_PER_REQ,
        "usable_cores": usable_cores,
        "uplift_note": (
            "shm removes the msgpack encode/decode and the HTTP body "
            "copy from the numeric path (one staged copy into the "
            "segment remains); on this container client and engines "
            f"timeshare {usable_cores} core(s), so the uplift is "
            "serialization savings only — the >=1.3x floor is a "
            "multi-core claim (tests/test_perf_floors.py gates it)"),
        "backend": jax.default_backend(),
    }


GBDT_DIST_ROWS = 100_000      # per host (2 hosts -> 200k global rows,
#                               HIGGS shape: the 100M-row flagship
#                               methodology at container scale)
GBDT_DIST_FEATS = 28          # the HIGGS feature width
GBDT_DIST_ITERS = 10


def bench_gbdt_dist() -> dict:
    """The PR 19 flagship: comm-efficient quantized-histogram
    distributed GBDT on the HIGGS-100M shape. Two REAL 2-process
    ``jax.distributed`` groups (tests/multihost_worker.py --bench-rows)
    each stream a per-host Arrow IPC row shard as memory-mapped
    ChunkedTable chunks through sketch binning — the raw f32 matrix
    never rematerializes — and train data-parallel over the group:

    - run A: the f32 psum engine (hist_bits=32, the pre-PR wire);
    - run B: quantized reduce-scatter (hist_bits=16, int16 wire,
      feature-partitioned split search).

    Reports per-phase walls, the modeled per-device collective bytes
    (ring model — the collectives run inside jit, so bytes are modeled
    from the static schedule, see docs/distributed_gbdt.md), the
    comm reduction (floor: >=2x), the ASSERTED streaming memory budget,
    and the hot-loop phase micro-timings observed through the
    ``gbdt_hist_phase_ms`` metric family and rendered through the real
    Prometheus exposition."""
    import subprocess
    import sys

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core import metrics as MC
    from mmlspark_tpu.core.prometheus import PromRenderer, \
        process_families

    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests")
    mh_worker = os.path.join(tests_dir, "multihost_worker.py")
    env = {**{k: v for k, v in os.environ.items()
              if not k.startswith(("JAX_", "XLA_"))},
           "JAX_PLATFORMS": "cpu"}

    def _free_port():
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def run_group(hist_bits, hist_comm):
        port = _free_port()
        t0 = time.perf_counter()
        procs = [subprocess.Popen(
            [sys.executable, mh_worker, str(port), str(pid), "2",
             "--timeout-s", "120",
             "--bench-rows", str(GBDT_DIST_ROWS),
             "--bench-feats", str(GBDT_DIST_FEATS),
             "--bench-iters", str(GBDT_DIST_ITERS),
             "--hist-bits", str(hist_bits), "--hist-comm", hist_comm],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for pid in range(2)]
        phases, comm, stat, rcs = {}, {}, {}, []
        for p in procs:
            out_txt, err_txt = p.communicate(timeout=1800)
            rcs.append(p.returncode)
            if p.returncode != 0:
                raise RuntimeError(
                    f"gbdt_dist worker failed:\n{out_txt}\n{err_txt}")
            for line in out_txt.splitlines():
                parts = line.split()
                if line.startswith("BENCH_PHASE") and parts[1] == "0":
                    phases[parts[2]] = float(parts[3])
                elif line.startswith("BENCH_COMM") and parts[1] == "0":
                    comm[parts[2]] = float(parts[3])
                elif line.startswith("BENCH_STAT") and parts[1] == "0":
                    stat = {"auc": float(parts[2]),
                            "raw_mb": float(parts[3]),
                            "peak_chunk_mb": float(parts[4]),
                            "maxrss_mb": float(parts[5])}
        wall = time.perf_counter() - t0
        # the streaming memory budget the scenario ASSERTS: chunks in
        # flight stay far under the raw shard (the matrix never
        # rematerializes between the Arrow mmap and the binned int8)
        assert stat["peak_chunk_mb"] * 4 < stat["raw_mb"], stat
        return {"wall_s": round(wall, 2), "phases": phases,
                "comm_bytes_per_device": comm, **stat}

    run_f32 = run_group(32, "psum")
    run_q16 = run_group(16, "reduce_scatter")
    tot_f32 = sum(run_f32["comm_bytes_per_device"].values())
    tot_q16 = sum(run_q16["comm_bytes_per_device"].values())
    reduction = tot_f32 / max(tot_q16, 1.0)
    assert reduction >= 2.0, (tot_f32, tot_q16)

    # hot-loop phase micro-timings (build/reduce/split): the phases
    # fuse inside one jitted program in the real engine, so they are
    # micro-timed here as standalone jits at the training shape and
    # observed through the gbdt_hist_phase_ms metric family
    from mmlspark_tpu.gbdt.histogram import build_histogram
    L, B, n_micro = 31, 63, 65536
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(
        0, B, size=(GBDT_DIST_FEATS, n_micro)), dtype=jnp.int32)
    qg = jnp.asarray(rng.integers(-16384, 16384, size=n_micro),
                     dtype=jnp.int16)
    qh = jnp.asarray(rng.integers(0, 16384, size=n_micro),
                     dtype=jnp.int16)
    w = jnp.ones(n_micro, jnp.int16)
    leaf = jnp.asarray(rng.integers(0, L, size=n_micro), jnp.int32)

    build = jax.jit(lambda: build_histogram(
        bins, qg, qh, w, leaf, L, B, method="scatter",
        count_values=w))
    hist = build().block_until_ready()

    reduce_ = jax.jit(lambda a, b: (
        a.astype(jnp.int16) + b.astype(jnp.int16)).astype(jnp.int32))
    half = (hist // 2).astype(jnp.int32)

    def _split(h):
        # the split-search core at gain time: dequantize once, cumsum,
        # gain table, flat argmax
        hf = h.astype(jnp.float32) * 1e-4
        gl = jnp.cumsum(hf[0], axis=-1)
        hl = jnp.cumsum(hf[1], axis=-1)
        gt, ht = gl[..., -1:], hl[..., -1:]
        gain = (gl ** 2 / (hl + 1.0)
                + (gt - gl) ** 2 / (ht - hl + 1.0))
        return jnp.argmax(gain.reshape(gain.shape[0], -1), axis=-1)

    split = jax.jit(_split)
    split(hist).block_until_ready()
    reduce_(half, half).block_until_ready()
    hists = MC.gbdt_hist_histograms()
    for _ in range(10):
        t0 = time.perf_counter()
        build().block_until_ready()
        hists["build"].observe((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        reduce_(half, half).block_until_ready()
        hists["reduce"].observe((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        split(hist).block_until_ready()
        hists["split"].observe((time.perf_counter() - t0) * 1e3)
    for coll, nb in run_q16["comm_bytes_per_device"].items():
        if nb:
            MC.gbdt_comm_add(coll, nb)
    r = PromRenderer()
    process_families(r)
    text = r.render()
    assert "gbdt_comm_bytes_total" in text
    assert "gbdt_hist_phase_ms_bucket" in text
    phase_ms = {ph: round(h.percentile(50), 3)
                for ph, h in hists.items()}

    usable_cores = len(os.sched_getaffinity(0))
    return {
        "metric": "gbdt_dist_quantized_comm_reduction",
        "value": round(reduction, 2),
        "unit": "x (modeled per-device collective bytes, f32 psum vs "
                "hist_bits=16 reduce_scatter, ring model)",
        "config": f"2 processes x {GBDT_DIST_ROWS} rows x "
                  f"{GBDT_DIST_FEATS} feats (HIGGS shape), "
                  f"{GBDT_DIST_ITERS} iters, 31 leaves, 63 bins, "
                  "Arrow ChunkedTable + sketch binning",
        "f32_psum": run_f32,
        "q16_reduce_scatter": run_q16,
        "auc_delta_q16_vs_f32": round(
            run_q16["auc"] - run_f32["auc"], 4),
        "hist_phase_ms_p50": phase_ms,
        "memory_budget": "asserted: peak in-flight chunk bytes * 4 < "
                         "raw per-host shard bytes (streamed, never "
                         "rematerialized)",
        "usable_cores": usable_cores,
        "backend": jax.default_backend(),
        "honesty_note": (
            "comm bytes are MODELED from the static collective "
            "schedule (ring costs; the collectives run inside jit on "
            "gloo CPU process groups here, not ICI) — the >=2x floor "
            "is the wire-payload contract, wall-clock uplift is a "
            f"TPU/multi-NIC claim; both processes timeshare "
            f"{usable_cores} core(s) on this container. MXU int8 "
            "histogram throughput claims are gated on TPU backends "
            "(tests/test_perf_floors.py)"),
    }


def bench_continuous() -> dict:
    """Closed-loop continuous training under drift (ref: TFX/Baylor
    continuous pipelines, KDD'17): a served logistic scorer, an
    injected distribution shift, and the ContinuousTrainer running the
    full drift -> refit -> shadow -> canary -> cutover loop
    autonomously while clients hammer the engine.

    Reports the numbers the robustness claim hangs on: loop reaction
    time (drift onset -> candidate serving), serving p99 during the
    refit/cutover window vs steady state (training must not perturb
    the request path), and the shadow-gate quality delta that justified
    the promotion."""
    import threading
    import urllib.request

    from mmlspark_tpu.core.metrics import DriftMonitor
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.linear import TPULogisticRegression
    from mmlspark_tpu.serving import (
        CanaryPolicy, ContinuousTrainer, GatePolicy, ModelRegistry,
        TriggerPolicy, json_scoring_pipeline, serve_model,
    )

    import jax

    d, shift = 8, 3.0
    rng = np.random.default_rng(0)
    w_true = np.linspace(1.0, -1.0, d)

    def blobs(n, mu):
        X = rng.normal(size=(n, d)) + mu
        y = (X @ w_true > mu * w_true.sum()).astype(np.float64)
        return X, y

    X0, y0 = blobs(2000, 0.0)
    est = TPULogisticRegression(maxIter=80)
    base = est.fit(DataTable({"features": X0, "label": y0}))
    dm = DriftMonitor.from_matrix(
        X0, feature_names=[f"f{i}" for i in range(d)])
    engine = serve_model(json_scoring_pipeline(base, drift_monitor=dm),
                         port=21900, batch_size=32, workers=2,
                         version="base")
    registry = ModelRegistry()

    def refit(window, active):
        tab = window.materialize()
        m = est.partial_fit(tab, getattr(active, "model", None))
        ndm = DriftMonitor.from_matrix(
            np.asarray(tab["features"]),
            feature_names=[f"f{i}" for i in range(d)])
        return json_scoring_pipeline(m, drift_monitor=ndm)

    trainer = ContinuousTrainer(
        engine, refit, registry=registry,
        triggers=TriggerPolicy(max_mean_delta_sigma=2.0,
                               min_window_rows=256, cooldown_s=1.0,
                               watch_slo_alerts=False),
        gate=GatePolicy(shadow_rows=512),
        canary=CanaryPolicy(fraction=0.5, min_batches=3,
                            decision_timeout_s=30),
        warmup_example={"features": [0.0] * d},
        poll_interval_s=0.05)

    lat_steady, lat_refit = [], []
    errors = [0]
    phase = {"mu": 0.0, "sink": lat_steady}
    stop = threading.Event()
    lock = threading.Lock()

    def client(tid):
        crng = np.random.default_rng(100 + tid)
        while not stop.is_set():
            x = crng.normal(size=d) + phase["mu"]
            body = json.dumps({"features": list(x)}).encode()
            req = urllib.request.Request(
                engine.source.address, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    phase["sink"].append(dt)
            except Exception:  # noqa: BLE001 — availability metric
                errors[0] += 1
            time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(4)]
    trainer.start()
    for t in threads:
        t.start()
    time.sleep(3.0)    # steady state on the base model

    # -- drift onset: traffic shifts, labeled rows reach the window ----
    with lock:
        phase["mu"] = shift
        phase["sink"] = lat_refit
    Xs, ys = blobs(2000, shift)
    drift_onset = time.perf_counter()
    for lo in range(0, 2000, 250):
        trainer.ingest(DataTable({"features": Xs[lo:lo + 250],
                                  "label": ys[lo:lo + 250]}))
    deadline = time.monotonic() + 120
    while trainer.promotions < 1 and time.monotonic() < deadline:
        time.sleep(0.05)
    reaction_s = time.perf_counter() - drift_onset
    time.sleep(1.0)    # tail of the cutover window
    stop.set()
    for t in threads:
        t.join(timeout=10)
    promoted = trainer.promotions >= 1
    shadow = next((e for e in registry.events
                   if getattr(e, "kind", "") == "shadow_pass"), None)
    verdict = dict(shadow.stats) if shadow is not None else {}
    status = trainer.status()
    trainer.stop()
    engine.stop()

    def p(v, q):
        return float(np.percentile(v, q)) if v else float("nan")

    return {
        "metric": "continuous_loop_reaction_s",
        "value": round(reaction_s, 2),
        "unit": "s (drift onset -> refit candidate serving live)",
        "promoted": promoted,
        "active_version": "ct-1" if promoted else "base",
        "serving_p99_ms": {
            "steady": round(p(lat_steady, 99), 2),
            "during_refit_cutover": round(p(lat_refit, 99), 2),
        },
        "serving_p50_ms": {
            "steady": round(p(lat_steady, 50), 2),
            "during_refit_cutover": round(p(lat_refit, 50), 2),
        },
        "requests": {"steady": len(lat_steady),
                     "during_refit_cutover": len(lat_refit),
                     "failed": errors[0]},
        "gate": {k: verdict.get(k) for k in
                 ("quality_candidate", "quality_baseline",
                  "quality_delta", "divergence", "nan_rate",
                  "shadow_rows")},
        "trigger": status.get("last_trigger"),
        "cycles": status.get("cycles"),
        "backend": jax.default_backend(),
    }


ADAPTIVE_DIM = 16
ADAPTIVE_CLASSES = 4
ADAPTIVE_FILLERS = 6          # zipf tail behind the hot 2-variant head
ADAPTIVE_CLIENTS = 6
ADAPTIVE_ROUNDS = 40          # lockstep rounds for the drain cadence


def bench_adaptive() -> dict:
    """SLO-adaptive serving (serving/variants.py + the continuous
    batcher): a zipf-weighted ramp over a 2-variant model (full f32 +
    quantized int8 behind one logical name), measuring

    - per-variant measured cost (ms/row) and the declared-cost ratio
      the selector trades on at equal SLO,
    - reply p99 ACROSS a forced variant flip (fast-burn injected, then
      cleared -> step_down, select, step_up on the timeline) with
      availability + zero cross-model replies over the whole run,
    - batcher occupancy: the same offered load driven in drain-cadence
      lockstep (every client waits for the whole round to drain — the
      old drain-then-block arrival shape) vs free-running continuous
      admission.

    CPU-honesty: on this container every engine thread timeshares the
    same core(s) and int8 matmuls run SLOWER than f32 (no MXU), so the
    cost/qps reduction is reported from the DECLARED TPU-relative
    costs while measured ms/row carries what this box actually did;
    the >=1x occupancy floor is the only claim asserted here."""
    import threading
    import urllib.error
    import urllib.request

    import jax

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving import (
        HTTPSource, ModelZoo, ServingEngine, VariantSelector,
    )
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline
    from mmlspark_tpu.stages.basic import Lambda

    rng = np.random.default_rng(11)
    x_warm = np.zeros((1, ADAPTIVE_DIM), np.float32)
    x_cal = rng.normal(size=(64, ADAPTIVE_DIM)).astype(np.float32)
    module = build_network({"type": "mlp", "features": [32],
                            "num_classes": ADAPTIVE_CLASSES})
    f32 = TPUModel.from_flax(
        module, module.init(jax.random.PRNGKey(0), x_warm),
        inputCol="features", outputCol="scores", batchSize=8)
    int8 = f32.quantize({"features": x_cal})

    zoo = ModelZoo(memory_probe=None)
    zoo.register_factory(
        "clf", "v1", lambda: json_scoring_pipeline(f32),
        metadata={"precision": "f32",
                  "warmup_example": {"features": x_warm}})
    zoo.register_factory(
        "clf_int8", "v1", lambda: json_scoring_pipeline(int8),
        metadata={"precision": "int8",
                  "warmup_example": {"features": x_warm}})

    def filler_stage(tag):
        def handle(table):
            return table.with_column(
                "reply", [{"model": tag} for _ in table["request"]])
        return Lambda.apply(handle)

    for i in range(ADAPTIVE_FILLERS):
        zoo.register_factory(f"f{i}", "v1",
                             (lambda i=i: filler_stage(f"f{i}")))

    class _BurnToggle:
        """The selector's fast-burn input, injectable on demand."""

        def __init__(self):
            self.burning = False
            self.alerts = self

        def active(self):
            if not self.burning:
                return []
            a = type("A", (), {})()
            a.rule, a.slo = "fast_burn", "latency"
            return [a]

    toggle = _BurnToggle()
    sel = VariantSelector(zoo, slo=toggle, decide_interval_s=0.1,
                          hold_s=1.0, pressure_limit=10_000)
    sel.declare("clf", ["clf", "clf_int8"], slo_ms=100.0,
                costs={"clf": 1.0, "clf_int8": 0.25})
    source = HTTPSource(port=0)
    engine = ServingEngine(source, zoo=zoo, variants=sel, batch_size=8,
                           max_wait_ms=2.0, workers=1, tracing=False,
                           slo=False).start()
    addr = source.address

    # zipf-weighted picks: the 2-variant head stays hot, fillers tail
    names = ["clf"] + [f"f{i}" for i in range(ADAPTIVE_FILLERS)]
    ranks = np.arange(1, len(names) + 1, dtype=np.float64)
    probs = 1.0 / ranks ** 1.2
    probs /= probs.sum()
    payload = json.dumps(
        {"features": rng.normal(size=ADAPTIVE_DIM).tolist()}).encode()
    lock = threading.Lock()
    wrong, failures = [], []

    def post_one(model):
        req = urllib.request.Request(
            addr, data=payload,
            headers={"Content-Type": "application/json",
                     "X-Model": model})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                served = r.headers.get("X-Model", "")
                r.read()
            if model == "clf":
                if served not in ("clf@v1", "clf_int8@v1"):
                    with lock:
                        wrong.append(served)
            elif not served.startswith(model):
                with lock:
                    wrong.append((model, served))
        except Exception as e:  # noqa: BLE001 — availability metric
            with lock:
                failures.append(str(e))
        return (time.perf_counter() - t0) * 1e3

    def run_phase(n_per_client, lockstep):
        """ADAPTIVE_CLIENTS clients x n_per_client zipf requests.
        ``lockstep`` reproduces the drain-then-block cadence: nobody
        starts round i+1 until the whole round i drained."""
        lats: list = []
        picks = rng.choice(names, size=(ADAPTIVE_CLIENTS,
                                        n_per_client), p=probs)
        barrier = threading.Barrier(ADAPTIVE_CLIENTS)

        def client(c):
            out = []
            for i in range(n_per_client):
                if lockstep:
                    barrier.wait()
                out.append(post_one(str(picks[c][i])))
            with lock:
                lats.extend(out)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(ADAPTIVE_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        total = ADAPTIVE_CLIENTS * n_per_client
        return {"qps": round(total / wall, 1),
                "p50_ms": round(float(np.percentile(lats, 50)), 2),
                "p99_ms": round(float(np.percentile(lats, 99)), 2),
                "requests": total}

    try:
        for _ in range(4):                      # warm both rungs' path
            post_one("clf")
        # occupancy: drain-cadence lockstep vs continuous admission of
        # the SAME offered load
        drain = run_phase(ADAPTIVE_ROUNDS, lockstep=True)
        cont = run_phase(ADAPTIVE_ROUNDS, lockstep=False)
        occupancy_ratio = round(cont["qps"] / drain["qps"], 2)

        # steady f32, then the forced flip under continuous load
        steady = run_phase(20, lockstep=False)
        active_before = sel.status()["clf"]["active"]
        stop = threading.Event()
        flip_lats: list = []

        def hammer():
            while not stop.is_set():
                dt = post_one("clf")
                with lock:
                    flip_lats.append(dt)

        threads = [threading.Thread(target=hammer)
                   for _ in range(ADAPTIVE_CLIENTS)]
        for t in threads:
            t.start()
        toggle.burning = True
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if sel.status()["clf"]["active"] != active_before:
                break
            time.sleep(0.05)
        flipped_to = sel.status()["clf"]["active"]
        time.sleep(1.0)              # degraded tier under load
        toggle.burning = False
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if sel.status()["clf"]["active"] == active_before:
                break
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join()
        recovered = sel.status()["clf"]["active"] == active_before
        st = sel.status()["clf"]
        profiles = {}
        for v in st["variants"]:
            prof = sel._profiles[v["variant"]]
            measured = prof.ms_per_row(sel.window_s)
            profiles[v["variant"]] = {
                "declared_cost": (v["cost"]
                                  if v["cost_source"] == "declared"
                                  else None),
                "measured_ms_per_row": (round(measured, 4)
                                        if measured is not None
                                        else None),
                "p99_ms": v["p99_ms"],
                "cost_source": v["cost_source"],
            }
        events = [e.kind for e in sel.events]
    finally:
        engine.stop()
        zoo.close()

    usable_cores = len(os.sched_getaffinity(0))
    total_reqs = (drain["requests"] + cont["requests"]
                  + steady["requests"] + len(flip_lats) + 4)
    availability = 1.0 - len(failures) / max(1, total_reqs)
    return {
        "metric": "adaptive_occupancy_continuous_vs_drain",
        "value": occupancy_ratio,
        "unit": "x (free-running continuous admission qps vs "
                "drain-then-block lockstep cadence, same offered "
                "load)",
        "occupancy": {"drain_cadence": drain, "continuous": cont},
        "steady": steady,
        "forced_flip": {
            "flipped_to": flipped_to,
            "recovered_to_preferred": recovered,
            "p99_ms_across_flip": round(
                float(np.percentile(flip_lats, 99)), 2) if flip_lats
                else None,
            "requests_during_flip": len(flip_lats),
            "events": events,
        },
        "variant_profiles": profiles,
        "declared_cost_ratio_int8_vs_f32": 0.25,
        "availability": round(availability, 4),
        "wrong_replies": len(wrong),
        "zipf_models": len(names),
        "clients": ADAPTIVE_CLIENTS,
        "usable_cores": usable_cores,
        "honesty_note": (
            "int8 on this CPU container is SLOWER than f32 (no MXU; "
            "PR 10 measured ~0.19x), so the cost/qps reduction at "
            "equal SLO rides the DECLARED TPU-relative costs "
            "(0.25x); measured ms/row above records what this box "
            f"did on {usable_cores} timeshared core(s). The >=1x "
            "occupancy floor and the flip-window p99 are the "
            "hardware-independent claims"),
        "backend": jax.default_backend(),
    }


# scenario registry for --scenarios (cheap subsets of the full bench:
# the serving/lifecycle numbers are measurable on any backend, the
# training-throughput scenarios only mean anything on the TPU chip)
SCENARIOS = {
    "cifar": lambda: ("secondary_cifar", bench_cifar()),
    "resnet": lambda: ("secondary_resnet", bench_resnet()),
    "lm": lambda: ("secondary_lm", bench_lm()),
    "serving": lambda: ("secondary_serving", bench_serving()),
    "swap": lambda: ("secondary_swap", bench_swap()),
    "automl": lambda: ("secondary_automl", bench_automl()),
    "pipeline": lambda: ("secondary_pipeline", bench_pipeline()),
    "observability": lambda: ("secondary_observability",
                              bench_observability()),
    "quant": lambda: ("secondary_quant", bench_quant()),
    "coldstart": lambda: ("secondary_coldstart", bench_coldstart()),
    "ingress": lambda: ("secondary_ingress", bench_ingress()),
    "zoo": lambda: ("secondary_zoo", bench_zoo()),
    "sharded": lambda: ("secondary_sharded", bench_sharded()),
    "fleet_procs": lambda: ("secondary_fleet_procs",
                            bench_fleet_procs()),
    "fabric": lambda: ("secondary_fabric", bench_fabric()),
    "gbdt_dist": lambda: ("secondary_gbdt_dist", bench_gbdt_dist()),
    "ooc": lambda: ("secondary_ooc", bench_ooc()),
    "continuous": lambda: ("secondary_continuous",
                           bench_continuous()),
    "adaptive": lambda: ("secondary_adaptive", bench_adaptive()),
}


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--scenarios", default="all",
        help="comma list from {cifar,resnet,lm,higgs,serving,swap,"
             "automl,pipeline,observability,quant,coldstart,ingress,"
             "zoo,sharded,fleet_procs,fabric,gbdt_dist,ooc,continuous} "
             "or 'all' (the full flagship bench)")
    args = ap.parse_args()
    if args.scenarios != "all":
        names = [n.strip() for n in args.scenarios.split(",")]
        if "sharded" in names and \
                os.environ.get("JAX_PLATFORMS", "") == "cpu":
            # the forced-host-device-count recipe must run BEFORE the
            # first backend use; real accelerators keep their topology
            from mmlspark_tpu.utils.jax_compat import \
                set_cpu_device_count
            set_cpu_device_count(SHARDED_MESH_DEVICES)
        configure_compile_cache()
        out = {"backend": None,
               "scenarios_run": sorted(args.scenarios.split(","))}
        # coldstart's children need the device, so it runs while this
        # process has not initialized a backend (a stable sort: the
        # rest keep the order given)
        for name in sorted(names, key=lambda n: n != "coldstart"):
            if name != "coldstart" and out["backend"] is None:
                import jax
                out["backend"] = jax.default_backend()
            if name == "higgs":
                higgs, auc, hist_method = bench_higgs_gbdt()
                out["secondary"] = {
                    "metric": "higgs1m_gbdt_train_wall_clock",
                    "value": higgs[63]["wall_s"], "unit": "s",
                    "hist_method": hist_method,
                    "synthetic_holdout_auc": round(auc, 4),
                    "phases": higgs[63]["phases"],
                    "bin_path": higgs[63]["bin_path"],
                    "host_bin_63": higgs["host_bin_63"],
                    "max_bin_255": higgs[255],
                }
                continue
            if name not in SCENARIOS:
                raise SystemExit(f"unknown scenario {name!r}")
            key, result = SCENARIOS[name]()
            out[key] = result
        if out["backend"] is None:      # coldstart alone: ask its child
            out["backend"] = out["secondary_coldstart"]["backend"]
        print(json.dumps(out))
        return
    _run_full()


def _run_full():
    configure_compile_cache()
    measured = _measured_baselines()
    cifar = bench_cifar()
    resnet = bench_resnet()
    lm = bench_lm()
    higgs, higgs_auc, hist_method = bench_higgs_gbdt()
    higgs_wall = higgs[63]["wall_s"]
    serving = bench_serving()
    automl = bench_automl()
    pipeline = bench_pipeline()

    per_chip = cifar["imgs_per_sec_per_chip"]
    gbdt_base = measured.get("higgs1m_sklearn_hgb_wall_s")
    gbdt_source = "measured:sklearn_hist_gradient_boosting"
    if not gbdt_base:
        gbdt_base, gbdt_source = BASELINE_HIGGS_WALL_S, "constant:lightgbm_cpu"

    result = {
        "metric": "cifar10_convnet_train_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
        "feed": "device-resident",
        "secondary": {
            "metric": "higgs1m_gbdt_train_wall_clock",
            "value": round(higgs_wall, 1),
            "unit": "s",
            "vs_baseline": round(gbdt_base / higgs_wall, 3),
            "baseline_wall_s": gbdt_base,
            "baseline_source": gbdt_source,
            # a native-LightGBM wall on THIS machine is not measurable:
            # lightgbm is not in the image and the environment has no
            # network egress (pip resolves no distribution). The sklearn
            # HistGradientBoosting baseline above is measured HERE and
            # clearly labeled; docs/lightgbm.md's own claim is relative
            # ("10-30% faster than SparkML GBT"), not absolute.
            "vs_lightgbm": "unmeasurable:no_lightgbm_in_image_no_egress",
            # AUC of the synthetic separable logit, NOT real HIGGS model
            # quality (accuracy gates live in tests/test_benchmarks.py)
            "synthetic_holdout_auc": round(higgs_auc, 4),
            "hist_method": hist_method,
            "config": f"{HIGGS_N}x{HIGGS_F}, 63 leaves, 63 bins, 40 iters",
            "phases": higgs[63]["phases"],
            "bin_path": higgs[63]["bin_path"],
            "boost_chunk": higgs[63]["boost_chunk"],
            "host_bin_63": higgs["host_bin_63"],
            "max_bin_255": higgs[255],
        },
    }
    for key in ("tflops_per_sec_per_chip", "mfu"):
        if key in cifar:
            result[key] = cifar[key]
    resnet_entry = {
        "metric": "cifar10_resnet20_train_imgs_per_sec_per_chip",
        "value": round(resnet["imgs_per_sec_per_chip"], 1),
        "unit": "imgs/sec/chip",
    }
    for key in ("tflops_per_sec_per_chip", "mfu"):
        if key in resnet:
            resnet_entry[key] = resnet[key]
    result["secondary_resnet"] = resnet_entry
    lm_entry = {
        "metric": "lm2048x8_train_tokens_per_sec_per_chip",
        "value": round(lm["tokens_per_sec_per_chip"], 1),
        "unit": "tokens/sec/chip",
        "config": (f"dim {LM_SPEC['dim']}, depth {LM_SPEC['depth']}, "
                   f"seq {LM_SEQ}, vocab {LM_SPEC['vocab_size']}, "
                   f"flash attention, bf16"),
    }
    for key in ("tflops_per_sec_per_chip", "mfu"):
        if key in lm:
            lm_entry[key] = lm[key]
    result["secondary_lm"] = lm_entry
    result["secondary_serving"] = serving
    result["secondary_automl"] = automl
    result["secondary_pipeline"] = pipeline
    if measured.get("cifar_convnet_torch_cpu_imgs_per_sec"):
        result["cpu_measured_baseline_imgs_per_sec"] = measured[
            "cifar_convnet_torch_cpu_imgs_per_sec"]

    print(json.dumps(result))


if __name__ == "__main__":
    main()
