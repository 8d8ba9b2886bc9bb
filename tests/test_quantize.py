"""Int8 post-training quantization + AOT serving executables.

Accuracy floors pin int8-vs-f32 prediction agreement on the CSV-harness
datasets (sklearn breast-cancer / digits / diabetes — the same real
datasets tests/test_benchmarks.py pins its metric floors on), AOT
artifacts must reproduce the in-process JIT path bit-for-bit per bucket
with ZERO jit traces at request time, and an f32 -> int8 rolling swap
under load must keep ``jit_cache_misses`` flat and availability >= 99%
while the precision/aot labels stay auditable end to end
(docs/quantized_inference.md).
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.table import DataTable


def _mlp_and_weights(features, num_classes, dim, seed=0):
    import jax
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"type": "mlp", "features": list(features),
                            "num_classes": num_classes})
    x0 = np.zeros((1, dim), np.float32)
    return module, module.init(jax.random.PRNGKey(seed), x0)


def _softmax(z):
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


class TestInt8Primitives:
    def test_per_channel_scales_and_roundtrip(self):
        from mmlspark_tpu.core.quantize import (
            per_channel_scales, quantize_weight,
        )
        rng = np.random.default_rng(0)
        W = rng.normal(size=(32, 5)) * np.array([1.0, 0.1, 10.0, 1e-30, 3.0])
        s = per_channel_scales(W)
        assert s.shape == (5,)
        assert (s > 0).all()         # dead channel clamped, not zero
        wq, ws = quantize_weight(W)
        assert wq.dtype == np.int8
        assert np.abs(wq).max() <= 127
        # dequantized weights within half a quantization step
        err = np.abs(wq.astype(np.float64) * ws - W)
        assert (err <= ws * 0.5 + 1e-12).all()

    def test_int8_matmul_device_matches_host_mirror(self):
        """Integer accumulation is exact, so the jitted device kernel
        and the numpy host mirror must agree bit-for-bit."""
        import jax
        from mmlspark_tpu.core.quantize import (
            act_scale, int8_matmul, int8_matmul_host, quantize_weight,
        )
        rng = np.random.default_rng(1)
        X = rng.normal(size=(64, 16)).astype(np.float32)
        W = rng.normal(size=(16, 7))
        wq, ws = quantize_weight(W)
        xs = act_scale(np.abs(X).max())
        dev = np.asarray(jax.jit(int8_matmul)(X, wq, xs, ws))
        host = int8_matmul_host(X, wq, xs, ws)
        assert np.array_equal(dev, host)
        # and it approximates the f32 matmul
        rel = np.abs(dev - X @ W).max() / np.abs(X @ W).max()
        assert rel < 0.05, rel

    def test_int8_dot_lowers_to_integer_matmul(self):
        """The kernel must lower as an int8 x int8 -> int32 dot_general
        (the MXU integer path), not a dequantize-then-f32-matmul."""
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.core.quantize import int8_matmul
        txt = jax.jit(int8_matmul).lower(
            jnp.zeros((8, 4)), jnp.zeros((4, 3), jnp.int8),
            jnp.float32(0.1), jnp.zeros((3,))).as_text()
        assert "tensor<8x4xi8>" in txt and "tensor<8x3xi32>" in txt

    def test_nan_rows_propagate_not_corrupt(self):
        """A NaN feature must yield NaN output from the int8 kernel —
        exactly like the f32 oracle — never a confident finite score
        (an int accumulator can't carry NaN; the epilogue re-injects)."""
        import jax
        from mmlspark_tpu.core.quantize import (
            act_scale, int8_matmul, int8_matmul_host, quantize_weight,
        )
        rng = np.random.default_rng(2)
        X = rng.normal(size=(8, 4)).astype(np.float32)
        X[2, 1] = np.nan
        wq, ws = quantize_weight(rng.normal(size=(4, 3)))
        xs = act_scale(1.0)
        for out in (np.asarray(jax.jit(int8_matmul)(X, wq, xs, ws)),
                    int8_matmul_host(X, wq, xs, ws)):
            assert np.isnan(out[2]).all(), out[2]
            assert np.isfinite(out[[0, 1, 3, 4, 5, 6, 7]]).all()

    def test_calibrator_percentile_and_thread_safety(self):
        from mmlspark_tpu.core.quantize import ActivationCalibrator
        cal = ActivationCalibrator(percentile=99.0)
        x = np.zeros(1000)
        x[-1] = 100.0               # outlier the percentile clips
        cal.observe("a", x)
        assert cal.amax()["a"] < 100.0
        exact = ActivationCalibrator()
        threads = [threading.Thread(
            target=lambda i=i: exact.observe("a", np.full(10, float(i))))
            for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert exact.amax()["a"] == 15.0


class TestAccuracyFloors:
    """Int8-vs-f32 agreement on the CSV-harness datasets: >= 99.5%
    top-1 agreement, bounded probability max-abs-err (idle-host
    measurements: breast-cancer 100% / 0.077, digits MLP 99.94% /
    0.079, diabetes max-rel-err 0.8%)."""

    def test_logistic_breast_cancer_agreement(self):
        from sklearn.datasets import load_breast_cancer
        from mmlspark_tpu.models.linear import TPULogisticRegression
        X, y = load_breast_cancer(return_X_y=True)
        t = DataTable({"features": X.astype(np.float64),
                       "label": y.astype(np.float64)})
        m = TPULogisticRegression(maxIter=150).fit(t)
        q = m.quantize(t)
        assert q.get("precision") == "int8"
        assert m.get("precision") == "f32"   # oracle untouched
        pf, pq = m.transform(t), q.transform(t)
        agree = (np.asarray(pf["prediction"])
                 == np.asarray(pq["prediction"])).mean()
        assert agree >= 0.995, agree
        perr = np.abs(np.asarray(pf["probability"])
                      - np.asarray(pq["probability"])).max()
        assert perr <= 0.15, perr

    def test_mlp_digits_agreement(self):
        import jax
        import jax.numpy as jnp
        import optax
        from sklearn.datasets import load_digits
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        X, y = load_digits(return_X_y=True)
        X = (X / 16.0).astype(np.float32)
        module = build_network({"type": "mlp", "features": [64, 32],
                                "num_classes": 10})
        params = module.init(jax.random.PRNGKey(0), X[:1])
        opt = optax.adam(1e-2)
        state = opt.init(params)

        @jax.jit
        def step(params, state, xb, yb):
            def loss(p):
                return optax.softmax_cross_entropy_with_integer_labels(
                    module.apply(p, xb), yb).mean()
            up, state2 = opt.update(jax.grad(loss)(params), state)
            return optax.apply_updates(params, up), state2

        xb, yb = jnp.asarray(X), jnp.asarray(y)
        for _ in range(60):
            params, state = step(params, state, xb, yb)
        m = TPUModel.from_flax(module, params, inputCol="features",
                               outputCol="scores", batchSize=256)
        q = m.quantize({"features": X[:256]})
        t = DataTable({"features": X})
        sf = np.asarray(m.transform(t)["scores"])
        sq = np.asarray(q.transform(t)["scores"])
        assert (sf.argmax(-1) == y).mean() >= 0.97   # real model, not noise
        agree = (sf.argmax(-1) == sq.argmax(-1)).mean()
        assert agree >= 0.995, agree
        assert np.abs(_softmax(sf) - _softmax(sq)).max() <= 0.15

    def test_linear_regression_diabetes_error_bound(self):
        from sklearn.datasets import load_diabetes
        from mmlspark_tpu.models.linear import TPULinearRegression
        X, y = load_diabetes(return_X_y=True)
        t = DataTable({"features": X, "label": y})
        m = TPULinearRegression(maxIter=200).fit(t)
        q = m.quantize(t)
        pf = np.asarray(m.transform(t)["prediction"])
        pq = np.asarray(q.transform(t)["prediction"])
        rel = np.abs(pf - pq).max() / np.abs(pf).max()
        assert rel <= 0.03, rel

    def test_quantized_model_save_load_roundtrip(self, tmp_path):
        """Quantized models must survive persistence (the lifecycle
        refresh flows save/load models): int8 arrays, scales, and the
        precision param all round-trip; predictions identical."""
        from sklearn.datasets import load_breast_cancer
        from mmlspark_tpu.core.serialize import load_stage, save_stage
        from mmlspark_tpu.models.linear import TPULogisticRegression
        X, y = load_breast_cancer(return_X_y=True)
        t = DataTable({"features": X, "label": y.astype(np.float64)})
        q = TPULogisticRegression(maxIter=50).fit(t).quantize(t)
        d = str(tmp_path / "qmodel")
        save_stage(q, d)
        q2 = load_stage(d)
        assert q2.get("precision") == "int8"
        assert q2.get("weights")["wq"].dtype == np.int8
        assert np.array_equal(np.asarray(q.transform(t)["prediction"]),
                              np.asarray(q2.transform(t)["prediction"]))

    def test_quantize_requires_flax_or_dense(self):
        from mmlspark_tpu.models.linear import TPULogisticRegressionModel
        from mmlspark_tpu.models.tpu_model import TPUModel
        m = TPUModel.from_fn(lambda w, ins: list(ins.values())[0],
                             {"w": np.ones(1)}, inputCol="x")
        with pytest.raises(ValueError, match="flax"):
            m.quantize({"x": np.ones((4, 2), np.float32)})
        sparse_model = TPULogisticRegressionModel(
            weights={"W": np.ones((4, 2)), "b": np.zeros(2)})
        with pytest.raises(ValueError, match="dense"):
            sparse_model.quantize(DataTable({"features": np.ones((4, 4))}))


class TestFusedQuantizedPipeline:
    def _fitted(self, n=4000, maxiter=60):
        from mmlspark_tpu.core.stage import Pipeline
        from mmlspark_tpu.models.linear import TPULogisticRegression
        from mmlspark_tpu.stages.dataprep import StandardScaler
        rng = np.random.default_rng(0)
        X = rng.normal(size=(n, 12))
        y = (X[:, 0] - 0.5 * X[:, 3]
             + 0.2 * rng.normal(size=n) > 0).astype(np.float64)
        t = DataTable({"features": X, "label": y})
        pm = Pipeline(stages=[
            StandardScaler(inputCol="features", outputCol="features"),
            TPULogisticRegression(featuresCol="features",
                                  labelCol="label", maxIter=maxiter),
        ]).fit(t)
        return pm, t

    def test_quantized_fused_bit_identical_to_staged_and_accurate(self):
        pm, t = self._fitted()
        fused = pm.fused(batch_size=64)
        qfused = fused.quantize(t.slice(0, 512))
        assert fused.precision == "f32"
        assert qfused.precision == "int8"
        out_q = qfused.transform(t)
        out_staged = qfused.transform_staged(t)
        # the PR 9 numerics contract holds for int8 segments too:
        # fused == stage-at-a-time bit-identical
        for c in ("rawPrediction", "probability", "prediction"):
            assert np.array_equal(np.asarray(out_q[c]),
                                  np.asarray(out_staged[c])), c
        out_f = fused.transform(t)
        agree = (np.asarray(out_f["prediction"])
                 == np.asarray(out_q["prediction"])).mean()
        assert agree >= 0.99, agree

    def test_quantized_serving_discipline(self):
        """Buckets, warmup, monotone jit_cache_misses, and the
        precision label survive quantization."""
        pm, t = self._fitted(n=512, maxiter=20)
        fused = pm.fused(batch_size=64)
        qfused = fused.quantize(t.slice(0, 128))
        assert qfused.bucket_sizes() == fused.bucket_sizes()
        compiles = qfused.warmup(t.slice(0, 1))
        assert compiles > 0
        before = qfused.jit_cache_misses
        qfused.transform(t.slice(0, 64))
        assert qfused.jit_cache_misses == before, \
            "steady-state quantized transform recompiled"
        assert qfused.metrics()["precision"] == "int8"

    def test_percentile_forwards_to_stage_hooks(self):
        """fused.quantize(calib, percentile=...) must reach the stage
        calibrators: a tighter clip percentile yields a smaller
        activation scale than the exact-max default."""
        pm, t = self._fitted(n=512, maxiter=10)
        fused = pm.fused(batch_size=64)
        # make the clip percentile matter: one outlier row
        X = np.asarray(t["features"]).copy()
        X[0] *= 50.0
        spiky = DataTable({"features": X, "label": t["label"]})
        exact = fused.quantize(spiky)
        clipped = fused.quantize(spiky, percentile=99.0)
        s_exact = exact.stages[-1].get("weights")["x_scale"]
        s_clip = clipped.stages[-1].get("weights")["x_scale"]
        assert s_clip < s_exact, (s_clip, s_exact)

    def test_serving_scorer_warmup_records_histogram(self):
        """The fused serving scorer's warmup must land per-bucket
        samples in model_warmup_ms too (the shared core/warmup.py
        loop), not just the batch-path warmups."""
        from mmlspark_tpu.core import metrics as MC
        from mmlspark_tpu.serving.fleet import json_scoring_pipeline
        pm, t = self._fitted(n=256, maxiter=10)
        stage = json_scoring_pipeline(pm, batch_size=32)
        hist = MC.warmup_histograms()["model_warmup_ms"]
        before = hist.summary().get("count", 0)
        compiles = stage.warmup(t.slice(0, 1))
        assert compiles > 0
        assert hist.summary()["count"] - before == \
            len(stage.scorer.fused.bucket_sizes())

    def test_quantize_without_quantizable_stage_raises(self):
        from mmlspark_tpu.core.fusion import FusedPipelineModel
        from mmlspark_tpu.stages.dataprep import StandardScaler
        t = DataTable({"features": np.ones((8, 2))})
        scaler = StandardScaler(inputCol="features",
                                outputCol="features").fit(t)
        with pytest.raises(ValueError, match="no quantizable"):
            FusedPipelineModel([scaler]).quantize(t)


class TestWarmupHistogram:
    def test_warmup_records_per_bucket_and_exports(self):
        import jax
        from mmlspark_tpu.core import metrics as MC
        from mmlspark_tpu.core.prometheus import PromRenderer, \
            process_families
        from mmlspark_tpu.models.tpu_model import TPUModel
        module, weights = _mlp_and_weights([16], 4, 8)
        m = TPUModel.from_flax(module, weights, inputCol="features",
                               outputCol="scores", batchSize=32)
        hist = MC.warmup_histograms()["model_warmup_ms"]
        before = hist.summary().get("count", 0)
        m.warmup({"features": np.zeros((1, 8), np.float32)})
        after = hist.summary()["count"]
        assert after - before == len(m.bucket_sizes())
        r = PromRenderer()
        process_families(r)
        assert "serving_model_warmup_ms_bucket" in r.render()


@pytest.fixture(scope="module")
def aot_artifact(tmp_path_factory):
    """One exported f32 MLP artifact shared by the AOT tests."""
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving import aot
    module, weights = _mlp_and_weights([64, 32], 10, 16)
    m = TPUModel.from_flax(module, weights, inputCol="features",
                           outputCol="scores", batchSize=64)
    art = str(tmp_path_factory.mktemp("aot") / "model_v1")
    manifest = aot.export_model(
        m, {"features": np.zeros((1, 16), np.float32)}, art,
        version="v1")
    return m, art, manifest


class TestAOTExportLoad:
    def test_manifest_and_artifact_layout(self, aot_artifact):
        _, art, manifest = aot_artifact
        assert manifest["kind"] == "tpu_model"
        assert manifest["format"] in ("jax_export", "trace_cache")
        assert manifest["precision"] == "f32"
        assert manifest["buckets"] == [8, 16, 32, 64]
        for name in ("manifest.json", "programs.pkl", "weights.pkl",
                     "model_fn.pkl", "example.pkl",
                     "example_request.json"):
            assert os.path.exists(os.path.join(art, name)), name

    def test_loaded_bit_identical_to_jit_zero_traces(self, aot_artifact):
        """The AOT acceptance contract: per-bucket outputs bit-identical
        to the in-process JIT path, with ZERO jit traces on the loaded
        model — at load, at warmup, and at request time."""
        import jax
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.parallel import mesh as mesh_lib
        from mmlspark_tpu.serving import aot
        m, art, _ = aot_artifact
        loaded = aot.load_model(art)
        assert loaded.aot is True
        # reference: same weights, same single-device mesh, jit path
        ref = TPUModel(modelFn=m.get("modelFn"),
                       weights=m.get("weights"), inputCol="features",
                       outputCol="scores", batchSize=64)
        ref.set_mesh(mesh_lib.make_mesh(
            {"data": 1}, devices=[jax.devices()[0]]))
        rng = np.random.default_rng(3)
        for b in (8, 32, 64):
            X = rng.normal(size=(b, 16)).astype(np.float32)
            t = DataTable({"features": X})
            a = np.asarray(loaded.transform(t)["scores"])
            r = np.asarray(ref.transform(t)["scores"])
            assert np.array_equal(a, r), f"bucket {b} diverged"
        assert loaded.warmup(
            {"features": np.zeros((1, 16), np.float32)}) == 0
        assert loaded.jit_cache_misses == 0, \
            "AOT-loaded model traced at request time"

    def test_unseen_shape_falls_back_and_counts(self, aot_artifact):
        """A shape the artifact never exported must still serve (lazy
        jit fallback) and must COUNT as a cache miss — the recompile
        guard stays meaningful on AOT replicas."""
        from mmlspark_tpu.serving import aot
        _, art, _ = aot_artifact
        loaded = aot.load_model(art)
        # 48 features instead of 16 would break the model; use a row
        # count above batchSize's bucket cap instead: cap bucket = 64,
        # still exported. Use a fresh model with batchSize raised so a
        # 128-bucket was never exported.
        loaded.set("batchSize", 128)
        X = np.zeros((100, 16), np.float32)
        out = loaded.transform(DataTable({"features": X}))
        assert np.asarray(out["scores"]).shape[0] == 100
        assert loaded.jit_cache_misses >= 1

    def test_quantized_model_roundtrip(self, tmp_path):
        from mmlspark_tpu.serving import aot
        from mmlspark_tpu.models.tpu_model import TPUModel
        module, weights = _mlp_and_weights([32], 4, 8)
        m = TPUModel.from_flax(module, weights, inputCol="features",
                               outputCol="scores", batchSize=16)
        rng = np.random.default_rng(0)
        calib = rng.normal(size=(64, 8)).astype(np.float32)
        q = m.quantize({"features": calib})
        art = str(tmp_path / "q_v1")
        manifest = aot.export_model(q, {"features": calib[:1]}, art,
                                    version="v1-int8")
        assert manifest["precision"] == "int8"
        loaded = aot.load_model(art)
        assert loaded.get("precision") == "int8"
        t = DataTable({"features": calib})
        a = np.asarray(loaded.transform(t)["scores"])
        import jax
        from mmlspark_tpu.parallel import mesh as mesh_lib
        q1 = q
        q1.set_mesh(mesh_lib.make_mesh({"data": 1},
                                       devices=[jax.devices()[0]]))
        r = np.asarray(q1.transform(t)["scores"])
        assert np.array_equal(a, r)
        assert loaded.jit_cache_misses == 0

    def test_bfloat16_module_roundtrip_stores_what_its_programs_read(
            self, tmp_path):
        """A module that computes in bfloat16 over float32 parameters:
        the exported programs consume the placed tree (kernels in
        bfloat16), so the artifact stores that tree, the replica places
        it as held with no model fn to read, and scores what the
        in-process model scores — on an exported bucket and through
        the lazy fallback on one that was not."""
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.parallel import mesh as mesh_lib
        from mmlspark_tpu.serving import aot
        module = build_network({
            "type": "transformer", "vocab_size": 64, "dim": 32, "depth": 1,
            "heads": 4, "max_len": 8, "num_classes": 3,
            "dtype": "bfloat16"})
        variables = jax.jit(module.init)(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 8), jnp.int32))
        m = TPUModel.from_flax(module, variables, inputCol="features",
                               outputCol="scores", batchSize=16)
        m.set_mesh(mesh_lib.make_mesh({"data": 1},
                                      devices=[jax.devices()[0]]))
        rows = np.random.default_rng(2).integers(
            0, 64, size=(13, 8)).astype(np.float32)
        art = str(tmp_path / "lm_v1")
        aot.export_model(m, {"features": rows[:1]}, art, version="v1")
        loaded = aot.load_model(art)
        stored = loaded.get("weights")["params"]
        assert str(stored["block_0"]["qkv"]["kernel"].dtype) == "bfloat16"
        assert str(stored["head"]["kernel"].dtype) == "float32"
        t = DataTable({"features": rows})
        want = np.asarray(m.transform(t)["scores"])
        assert np.array_equal(np.asarray(loaded.transform(t)["scores"]),
                              want)
        assert loaded.jit_cache_misses == 0
        assert loaded.metrics()["weights_cast_leaves"] == 0
        assert m.metrics()["weights_cast_leaves"] == 9
        assert loaded.resident_bytes() == m.resident_bytes()
        # a bucket the artifact never saw: the fallback traces the real
        # fn over the stored tree and finds nothing left to convert
        loaded.set("batchSize", 32)
        m.set("batchSize", 32)
        wide = DataTable({"features": np.tile(rows, (2, 1))})
        assert np.array_equal(
            np.asarray(loaded.transform(wide)["scores"]),
            np.asarray(m.transform(wide)["scores"]))
        assert loaded.jit_cache_misses == 1
        assert loaded.metrics()["weights_cast_leaves"] == 0

    def test_pipeline_artifact_serves_end_to_end(self, tmp_path):
        """Pipeline-kind artifact: the fused serving programs load
        pre-compiled, the scorer warms with zero compiles, and replies
        match the in-process scorer."""
        from mmlspark_tpu.core.stage import Pipeline
        from mmlspark_tpu.models.linear import TPULogisticRegression
        from mmlspark_tpu.serving import aot
        from mmlspark_tpu.serving.fleet import json_scoring_pipeline
        from mmlspark_tpu.stages.dataprep import StandardScaler
        rng = np.random.default_rng(0)
        X = rng.normal(size=(1024, 6))
        y = (X[:, 0] > 0).astype(np.float64)
        t = DataTable({"features": X, "label": y})
        pm = Pipeline(stages=[
            StandardScaler(inputCol="features", outputCol="features"),
            TPULogisticRegression(featuresCol="features",
                                  labelCol="label", maxIter=30),
        ]).fit(t)
        example = DataTable({"features": X[:1]})
        art = str(tmp_path / "pipe_v1")
        manifest = aot.export_model(pm.fused(batch_size=32), example,
                                    art, version="v1")
        assert manifest["kind"] == "pipeline"
        loaded = aot.load_model(art)
        assert loaded.aot is True
        stage = json_scoring_pipeline(loaded)
        assert stage.aot is True
        # serving warmup through the exact hot path: zero compiles
        assert stage.warmup(example) == 0
        assert loaded.jit_cache_misses == 0
        # replies match the in-process (jit) scorer
        ref_stage = json_scoring_pipeline(pm)
        body = json.dumps({"features": [float(v) for v in X[1]]}).encode()
        req = DataTable({"id": ["r1"], "request": [{"entity": body}]})
        got = stage.transform(req)["reply"][0]
        want = ref_stage.transform(req)["reply"][0]
        assert got == want
        assert loaded.jit_cache_misses == 0, \
            "AOT pipeline traced at request time"


class TestQuantSwapChaos:
    def test_f32_to_int8_rolling_swap_under_load(self):
        """The acceptance drill: an f32 -> int8 rollout under live load
        keeps availability >= 99% and ``jit_cache_misses`` flat outside
        the swap's own warmup, and every audit surface (healthz,
        serving_model_info, registry, SwapEvent) shows the precision
        flip."""
        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.serving.fleet import (
            ServingFleet, json_scoring_pipeline,
        )
        from mmlspark_tpu.serving.lifecycle import (
            CanaryPolicy, ModelRegistry,
        )
        dim = 8
        module = build_network({"type": "mlp", "features": [16],
                                "num_classes": 4})
        x0 = np.zeros((1, dim), np.float32)
        m = TPUModel.from_flax(
            module, module.init(jax.random.PRNGKey(0), x0),
            inputCol="features", outputCol="scores", batchSize=16)
        rng = np.random.default_rng(0)
        calib = rng.normal(size=(64, dim)).astype(np.float32)
        q = m.quantize({"features": calib})
        m.warmup({"features": x0})
        registry = ModelRegistry()
        registry.register("v1", json_scoring_pipeline(m))
        registry.register("v1-int8", json_scoring_pipeline(q))
        assert registry.metadata("v1-int8")["precision"] == "int8"
        fleet = ServingFleet(registry.get("v1"), n_engines=2,
                             base_port=19720, batch_size=16,
                             max_wait_ms=2.0, version="v1")
        payload = {"features": [0.1] * dim}
        results = {}
        try:
            for _ in range(8):
                assert "prediction" in fleet.post(payload)
            misses_f32 = m.jit_cache_misses

            def client(cid):
                for j in range(30):
                    try:
                        results[(cid, j)] = "prediction" in fleet.post(
                            payload, timeout=10)
                    except Exception:  # noqa: BLE001
                        results[(cid, j)] = False

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            report = fleet.rolling_swap(
                registry.get("v1-int8"), "v1-int8",
                warmup_example={"features": x0},
                policy=CanaryPolicy(fraction=0.5, min_batches=2,
                                    decision_timeout_s=30))
            for t in threads:
                t.join(timeout=60)
            assert report["ok"], report
            misses_int8 = q.jit_cache_misses
            for _ in range(8):       # post-swap steady state on int8
                assert "prediction" in fleet.post(payload)
            assert m.jit_cache_misses == misses_f32, \
                "f32 model recompiled during the int8 rollout"
            assert q.jit_cache_misses == misses_int8, \
                "int8 model compiled on the hot path after its warmup"
            assert misses_int8 > 0
            agg = fleet.metrics()["aggregate"]
            assert agg["precisions"] == ["int8", "int8"]
            for engine in fleet.engines:
                _, snap = engine._lifecycle_snapshot()
                assert snap["precision"] == "int8"
                assert snap["model_version"] == "v1-int8"
                info = [ln for ln in engine.metrics_text().splitlines()
                        if ln.startswith("serving_model_info")]
                assert any('precision="int8"' in ln for ln in info)
                event = engine.swap_events[-1]
                assert event.from_precision == "f32"
                assert event.to_precision == "int8"
        finally:
            fleet.stop_all()
        ok = sum(results.values())
        assert ok / len(results) >= 0.99, f"availability {ok}/{len(results)}"


class TestKernelAuditQuantized:
    def _chk(self):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import check_fusion_kernels as chk
        return chk

    def test_f64_upcast_caught_in_quantized_kernel(self):
        chk = self._chk()
        src = ("def bad(consts, env):\n"
               "    acc = env['x']\n"
               "    return {'y': acc.astype(jnp.float64) * consts['s']}\n")
        violations = chk._check_source("quantize.poison", src, 1,
                                       src.splitlines(True))
        assert any("f64 upcast" in v for v in violations), violations

    def test_f64_rule_scoped_to_quantized_kernels(self):
        chk = self._chk()
        src = ("def fine(consts, env):\n"
               "    return {'y': env['x'].astype(jnp.float64)}\n")
        violations = chk._check_source("SomeStage:uid", src, 1,
                                       src.splitlines(True))
        assert violations == [], violations

    def test_registered_quantized_kernels_clean(self):
        chk = self._chk()
        chk.register_known_callees()
        from mmlspark_tpu.core.fusion import KERNEL_REGISTRY
        names = set(KERNEL_REGISTRY.values())
        assert "quantize.int8_matmul" in names
        assert "quantize.quantize_act" in names
        violations = [v for v in chk.check_registered_kernels()
                      if "quantize" in v]
        assert violations == [], violations
