import numpy as np
import pytest

import jax

from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.core.stage import load_stage
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.models.learner import TPULearner
from mmlspark_tpu.parallel import mesh as mesh_lib
from mmlspark_tpu.testing.datagen import generate_classification_table


def _toy_table(n=256, d=16, classes=4, seed=0):
    return generate_classification_table(n, d, classes, seed=seed)


def _accuracy(model, table, label_col="label"):
    out = model.transform(table)
    pred = np.argmax(out["scores"], axis=1)
    return float(np.mean(pred == np.asarray(table[label_col])))


def test_mlp_learns_separable_data():
    t = _toy_table()
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=8, batchSize=64, learningRate=0.05, optimizer="momentum",
        computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    acc = _accuracy(model, t)
    assert acc > 0.9, f"accuracy {acc}"
    assert learner.history, "loss history should be recorded"


def test_dp_mesh_training_matches_quality():
    t = _toy_table(seed=1)
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=8, batchSize=64, learningRate=0.05,
        computeDtype="float32", logEvery=1000)
    learner.set_mesh(mesh_lib.make_mesh({"data": 8}))
    model = learner.fit(t)
    assert _accuracy(model, t) > 0.9


def test_fsdp_sharding():
    t = _toy_table(seed=2)
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=6, batchSize=64, learningRate=0.05,
        computeDtype="float32", paramSharding="fsdp", logEvery=1000)
    learner.set_mesh(mesh_lib.make_mesh({"data": 2, "fsdp": 4}))
    model = learner.fit(t)
    assert _accuracy(model, t) > 0.85


def test_convnet_on_images():
    rng = np.random.default_rng(0)
    n = 64
    # class-dependent mean images
    labels = rng.integers(0, 2, n)
    imgs = (rng.normal(size=(n, 8, 8, 3)) + labels[:, None, None, None] * 2.0)
    imgs = np.clip((imgs + 3) * 40, 0, 255).astype(np.uint8)
    rows = [ImageSchema.make_row(f"i{i}.png", imgs[i]) for i in range(n)]
    t = DataTable({"image": rows, "label": labels.astype(np.int64)})
    learner = TPULearner(
        featuresCol="image",
        networkSpec={"type": "convnet", "conv_features": [8],
                     "dense_features": [16], "num_classes": 2},
        epochs=25, batchSize=32, learningRate=0.1,
        computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    acc = _accuracy(model, t)
    assert acc > 0.9, f"accuracy {acc}"


def test_resnet_batchnorm_smoke():
    rng = np.random.default_rng(1)
    n = 32
    labels = rng.integers(0, 2, n)
    imgs = rng.normal(size=(n, 8, 8, 3)).astype(np.float32)
    t = DataTable({"features": imgs.reshape(n, -1), "label": labels})
    learner = TPULearner(
        networkSpec={"type": "resnet", "stage_sizes": [1], "width": 8,
                     "num_classes": 2},
        inputShape=[8, 8, 3],
        epochs=1, batchSize=16, computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    out = model.transform(t)
    assert out["scores"].shape == (n, 2)
    assert np.all(np.isfinite(out["scores"]))


def test_regression_mse():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    w = rng.normal(size=8)
    y = (x @ w).astype(np.float32)
    t = DataTable({"features": x, "label": y})
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 1},
        loss="mse", epochs=20, batchSize=64, learningRate=0.01,
        optimizer="adam", computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    pred = model.transform(t)["scores"][:, 0]
    resid = np.mean((pred - y) ** 2) / np.var(y)
    assert resid < 0.2, f"relative mse {resid}"


def test_streaming_shard_ingestion():
    # shard iterator feed: datasets that never materialize in one table
    # (the HDFS-staged feed analog, ref: CNTKLearner.scala:123-140)
    t = _toy_table()
    shards = list(t.shards(4))
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=8, batchSize=64, learningRate=0.05, optimizer="momentum",
        computeDtype="float32", logEvery=1000)
    model = learner.fit(shards)                 # list of shard tables
    acc = _accuracy(model, t)
    assert acc > 0.9, f"accuracy {acc}"

    learner2 = TPULearner(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=8, batchSize=64, learningRate=0.05, optimizer="momentum",
        computeDtype="float32", logEvery=1000)
    model2 = learner2.fit(lambda: iter(t.shards(3)))   # callable factory
    assert _accuracy(model2, t) > 0.9


def test_profile_dir_emits_trace(tmp_path):
    from mmlspark_tpu.utils.profiling import trace_files
    t = _toy_table()
    trace_dir = str(tmp_path / "prof")
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [8], "num_classes": 4},
        epochs=1, batchSize=64, computeDtype="float32",
        logEvery=1000, profileDir=trace_dir)
    learner.fit(t)
    assert trace_files(trace_dir), "no xplane trace emitted by training"


def test_checkpoint_resume(tmp_path):
    t = _toy_table(seed=4)
    ck = str(tmp_path / "ckpt")
    # constant schedule so the interrupted run's lr trajectory matches the
    # full run's (cosine depends on total_steps, which differs)
    common = dict(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=4, batchSize=64, learningRate=0.05, computeDtype="float32",
        schedule="constant",
        checkpointDir=ck, checkpointEvery=4, logEvery=1000, seed=9)
    full = TPULearner(**common).fit(t)

    # simulate crash: train with same config but stop early via epochs=2
    import shutil
    shutil.rmtree(ck)
    partial_learner = TPULearner(**{**common, "epochs": 2})
    partial_learner.fit(t)
    # now resume with the full epoch budget; should fast-forward & finish
    resumed = TPULearner(**common).fit(t)

    f = np.asarray(full.transform(t)["scores"])
    r = np.asarray(resumed.transform(t)["scores"])
    np.testing.assert_allclose(f, r, rtol=1e-3, atol=1e-3)


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    """A corrupt/truncated newest checkpoint must not kill resume:
    fit() logs, falls back to the PREVIOUS checkpoint, and finishes
    (the all-corrupt -> fresh-init twin runs against webdav in
    tests/test_remote_fs.py)."""
    import os
    t = _toy_table(seed=4)
    ck = str(tmp_path / "ckpt")
    common = dict(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=2, batchSize=64, learningRate=0.05, computeDtype="float32",
        schedule="constant",
        checkpointDir=ck, checkpointEvery=4, logEvery=1000, seed=9)
    TPULearner(**common).fit(t)               # 8 steps -> ckpts @ 4, 8
    steps = sorted(d for d in os.listdir(ck) if d.startswith("step_"))
    assert len(steps) >= 2, "need >= 2 checkpoints for the fallback"
    # truncate the NEWEST checkpoint's leaves mid-file (crash-mid-save)
    newest = os.path.join(ck, steps[-1], "leaves.npz")
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    prev_step = int(steps[-2].rsplit("_", 1)[1])
    newest_step = int(steps[-1].rsplit("_", 1)[1])
    # logEvery=1: every step lands in history, so the first logged
    # step IS the resume point
    resumed_learner = TPULearner(**{**common, "epochs": 4,
                                    "logEvery": 1})
    model = resumed_learner.fit(t)            # no raise: previous ckpt
    assert model is not None
    assert resumed_learner.history, "training never ran"
    first = min(h["step"] for h in resumed_learner.history)
    # resumed from the PREVIOUS checkpoint: past it, not past the
    # corrupt newest one (which a successful load would skip to)
    assert prev_step < first <= newest_step, (
        first, prev_step, newest_step)


def test_learned_model_roundtrip(tmp_path):
    t = _toy_table(seed=5)
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=2, batchSize=64, computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    out1 = model.transform(t)["scores"]
    p = str(tmp_path / "m")
    model.save(p)
    model2 = load_stage(p)
    out2 = model2.transform(t)["scores"]
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


def test_bilstm_tagger_smoke():
    rng = np.random.default_rng(0)
    n, T, V, K = 32, 12, 50, 3
    toks = rng.integers(0, V, size=(n, T)).astype(np.float32)
    # simple rule: tag = token mod K
    tags = (toks.astype(np.int64) % K)
    t = DataTable({"features": toks, "label": tags.astype(np.int64)})
    learner = TPULearner(
        networkSpec={"type": "bilstm", "vocab_size": V, "embed_dim": 16,
                     "hidden": 16, "num_tags": K},
        loss="token_cross_entropy",
        epochs=40, batchSize=16, learningRate=0.02, optimizer="adam",
        computeDtype="float32", logEvery=1000)
    model = learner.fit(t)
    out = model.transform(t)
    scores = np.asarray(out["scores"])
    assert scores.shape == (n, T, K)
    acc = float(np.mean(np.argmax(scores, -1) == tags))
    assert acc > 0.8, f"token accuracy {acc}"


def test_device_feed_matches_host_quality():
    t = _toy_table(seed=6)
    common = dict(
        networkSpec={"type": "mlp", "features": [32], "num_classes": 4},
        epochs=8, batchSize=64, learningRate=0.05,
        computeDtype="float32", logEvery=1000)
    learner = TPULearner(**common, dataFeed="device")
    learner.set_mesh(mesh_lib.make_mesh({"data": 8}))
    model = learner.fit(t)
    assert _accuracy(model, t) > 0.9
    # the learner times what it did and judges nothing: FLOP counts and
    # peaks live with the benchmark
    timing = learner.timing
    assert timing["steps_timed"] > 0 and timing["wall_s"] > 0
    assert timing["examples_per_sec"] > 0
    assert not [k for k in timing if "flop" in k.lower() or "mfu" in k]


def test_device_feed_compiles_only_its_chunk_program():
    """One scan length -> one chunk program, and no program of a bare
    train_step beside it."""
    import jax.monitoring as jmon
    compiled = []
    watching = {"on": True}
    jmon.register_event_duration_secs_listener(
        lambda name, _secs, fun_name="", **kw: compiled.append(fun_name)
        if watching["on"] and name.endswith("backend_compile_duration")
        else None)
    try:
        TPULearner(
            networkSpec={"type": "mlp", "features": [8], "num_classes": 4},
            epochs=2, batchSize=64, learningRate=0.05,
            computeDtype="float32", logEvery=1000,
            dataFeed="device").fit(_toy_table(seed=8))
    finally:
        watching["on"] = False
    assert compiled.count("jit(learner_chunk)") == 1, compiled
    assert not [n for n in compiled if "train_step" in n], compiled


def test_device_feed_checkpoint_resume(tmp_path):
    t = _toy_table(seed=7)
    ck = str(tmp_path / "ckpt")
    common = dict(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=4, batchSize=64, learningRate=0.05, computeDtype="float32",
        schedule="constant", dataFeed="device",
        checkpointDir=ck, checkpointEvery=4, logEvery=1000, seed=9)
    full = TPULearner(**common).fit(t)

    import shutil
    shutil.rmtree(ck)
    TPULearner(**{**common, "epochs": 2}).fit(t)
    resumed = TPULearner(**common).fit(t)

    f = np.asarray(full.transform(t)["scores"])
    r = np.asarray(resumed.transform(t)["scores"])
    np.testing.assert_allclose(f, r, rtol=1e-3, atol=1e-3)


def test_device_feed_rejects_streaming_and_remainder_is_masked():
    t = _toy_table(n=100, seed=8)  # 100 rows, batch 64 -> padded batch
    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=6, batchSize=64, learningRate=0.05, computeDtype="float32",
        logEvery=1000, dataFeed="device")
    model = learner.fit(t)
    assert _accuracy(model, t) > 0.8
    shards = [t.slice(0, 50), t.slice(50, 100)]
    bad = TPULearner(
        networkSpec={"type": "mlp", "features": [16], "num_classes": 4},
        epochs=1, batchSize=64, dataFeed="device")
    with pytest.raises(ValueError, match="device"):
        bad.fit(shards)
