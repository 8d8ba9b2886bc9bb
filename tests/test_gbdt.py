"""GBDT engine tests.

Modeled on the reference's LightGBM suites: small-data correctness plus
benchmark-CSV-style accuracy floors
(ref: src/lightgbm/src/test/resources/benchmarks_VerifyLightGBMClassifier.csv
— e.g. breast-cancer AUC 0.9925) and the distributed-without-a-cluster
pattern (ref: SURVEY.md §4 — partitions as nodes on localhost; here:
shard_map over the 8-device virtual CPU mesh).
"""

import numpy as np
import pytest

from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.gbdt import (
    BinMapper, Booster, TPUBoostClassifier, TPUBoostRegressor, train,
)
from mmlspark_tpu.gbdt.histogram import build_histogram
from mmlspark_tpu.parallel import mesh as mesh_lib

import jax.numpy as jnp


def _auc(y, p):
    from sklearn.metrics import roc_auc_score
    return roc_auc_score(y, p)


@pytest.fixture(scope="module")
def breast_cancer():
    from sklearn.datasets import load_breast_cancer
    return load_breast_cancer(return_X_y=True)


class TestBinning:
    def test_few_distinct_values(self):
        X = np.asarray([[0.0], [1.0], [1.0], [2.0]])
        m = BinMapper.fit(X, max_bin=255)
        assert m.num_bins[0] == 3
        b = m.transform(X)
        assert list(b[:, 0]) == [0, 1, 1, 2]

    def test_quantile_bins_roughly_equal(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(10_000, 1))
        m = BinMapper.fit(X, max_bin=16)
        b = m.transform(X)
        counts = np.bincount(b[:, 0], minlength=16)
        assert counts.min() > 300  # ~625 expected per bin

    def test_nan_goes_to_bin_zero(self):
        X = np.asarray([[np.nan], [1.0], [2.0]])
        m = BinMapper.fit(X, max_bin=8)
        assert m.transform(X)[0, 0] == 0

    def test_threshold_value_separates(self):
        X = np.linspace(0, 1, 100).reshape(-1, 1)
        m = BinMapper.fit(X, max_bin=10)
        b = m.transform(X)
        thr = m.bin_threshold_value(0, 4)
        lhs = X[b[:, 0] <= 4, 0]
        rhs = X[b[:, 0] > 4, 0]
        assert lhs.max() <= thr < rhs.min()

    def test_json_roundtrip(self):
        X = np.random.default_rng(1).normal(size=(500, 3))
        m = BinMapper.fit(X, max_bin=32)
        m2 = BinMapper.from_json(m.to_json())
        assert np.array_equal(m.transform(X), m2.transform(X))

    def test_f32_safety_detection(self):
        rng = np.random.default_rng(0)
        normal = rng.normal(size=(500, 2))
        assert BinMapper.fit(normal, max_bin=32).f32_safe()
        # unix-timestamp scale: 1s resolution needs >24 mantissa bits
        ts = (1.7e9 + rng.integers(0, 600, size=(2000, 1))).astype(float)
        assert not BinMapper.fit(ts, max_bin=255).f32_safe()
        # isolated sub-f32-resolution pair between wide gaps: the cut at
        # (1.0 + 1.000000005)/2 can't separate the pair in f32, even
        # though boundary-to-boundary spacing looks wide
        tight = np.asarray([1.0, 1.0 + 1e-8, 2.0] * 100)[:, None]
        assert not BinMapper.fit(tight, max_bin=8).f32_safe()
        # round-trip keeps the flag
        m = BinMapper.fit(tight, max_bin=8)
        assert not BinMapper.from_json(m.to_json()).f32_safe()

    def test_f32_snap_preserves_ulp_adjacent_splits(self):
        # f32 input snaps cuts DOWN to the largest f32 <= cut: two
        # 1-ulp-adjacent distinct values must stay in different bins
        # (round-to-nearest snapping could round the midpoint cut UP
        # onto the upper value and merge them), and the assignment must
        # equal what the unsnapped f64 midpoint cuts give
        a = np.float32(1.0) + np.float32(2.0) ** -23
        b = np.float32(1.0) + np.float32(2.0) ** -22
        X32 = np.array([a] * 5 + [b] * 5, np.float32)[:, None]
        m32 = BinMapper.fit(X32, max_bin=4)
        assert m32.f32_cuts_exact
        bins32 = m32.transform(X32)
        assert bins32[0, 0] != bins32[5, 0], "ulp-adjacent values merged"
        m64 = BinMapper.fit(X32.astype(np.float64), max_bin=4)
        np.testing.assert_array_equal(
            bins32, m64.transform(X32.astype(np.float64)))

    def test_legacy_model_f64_inference_heuristic(self, breast_cancer):
        # models saved before the fit-time flag fall back to threshold
        # heuristics: magnitude >= 2^24 forces f64; near-equal
        # thresholds on DIFFERENT features must not
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 3}, X, y)
        legacy = Booster.from_string(b.model_to_string())
        legacy.params.pop("f32_unsafe", None)
        assert not legacy._needs_f64_inference()
        # widely-spaced timestamp thresholds: magnitude rule kicks in
        legacy.trees["threshold"] = np.where(
            legacy.trees["is_leaf"], 0.0,
            1.7e9 + legacy.trees["threshold"])
        legacy._f64_flag = None   # the verdict is cached; trees mutated
        assert legacy._needs_f64_inference()
        # cross-feature near-equal thresholds: per-feature grouping
        # avoids the false positive
        legacy2 = Booster.from_string(b.model_to_string())
        legacy2.params.pop("f32_unsafe", None)
        thr = legacy2.trees["threshold"]
        internal = ~legacy2.trees["is_leaf"].astype(bool)
        idx = np.argwhere(internal)
        a_, b_ = idx[0], idx[1]
        legacy2.trees["feature"][tuple(a_)] = 0
        legacy2.trees["feature"][tuple(b_)] = 1
        thr[tuple(a_)] = 1000.0
        thr[tuple(b_)] = 1000.00001
        assert not legacy2._needs_f64_inference()

    def test_large_magnitude_features_bin_correctly(self):
        # the f32-unsafe fallback must keep full split resolution
        rng = np.random.default_rng(1)
        n = 2000
        ts = 1.7e9 + rng.integers(0, 600, size=n).astype(float)
        y = (ts % 600 > 300).astype(float)
        b = train({"objective": "binary", "num_iterations": 30,
                   "min_data_in_leaf": 5}, ts[:, None], y)
        assert _auc(y, b.predict(ts[:, None])) > 0.99


class TestHistogram:
    def test_scatter_matches_numpy(self):
        rng = np.random.default_rng(0)
        n, f, L, B = 200, 3, 4, 8
        bins = rng.integers(0, B, size=(n, f)).astype(np.int32)
        grad = rng.normal(size=n).astype(np.float32)
        hess = rng.uniform(0.1, 1, size=n).astype(np.float32)
        w = (rng.random(n) < 0.8).astype(np.float32)
        leaf = rng.integers(0, L, size=n).astype(np.int32)
        hist = np.asarray(build_histogram(
            jnp.asarray(bins.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(w), jnp.asarray(leaf), L, B, method="scatter"))
        # numpy reference
        ref = np.zeros((3, L, f, B), np.float64)
        for i in range(n):
            for j in range(f):
                ref[0, leaf[i], j, bins[i, j]] += grad[i] * w[i]
                ref[1, leaf[i], j, bins[i, j]] += hess[i] * w[i]
                ref[2, leaf[i], j, bins[i, j]] += w[i]
        np.testing.assert_allclose(hist, ref, rtol=1e-4, atol=1e-4)

    def test_onehot_matches_scatter(self):
        rng = np.random.default_rng(1)
        n, f, L, B = 500, 4, 6, 16
        bins = jnp.asarray(rng.integers(0, B, size=(f, n)), jnp.int32)
        grad = jnp.asarray(rng.normal(size=n), jnp.float32)
        hess = jnp.asarray(rng.uniform(0.1, 1, size=n), jnp.float32)
        w = jnp.ones(n, jnp.float32)
        leaf = jnp.asarray(rng.integers(0, L, size=n), jnp.int32)
        h1 = build_histogram(bins, grad, hess, w, leaf, L, B, "scatter")
        h2 = build_histogram(bins, grad, hess, w, leaf, L, B, "onehot")
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("n,f,L,B", [
        (700, 20, 6, 16),     # n > ROW_CHUNK: row-chunk accumulation
        (600, 20, 1, 256),    # B=256: single-leaf digit-decomposition
        (600, 20, 1, 160),    # b_pad=160: non-power-of-2 nibble (l=80)
        (600, 20, 1, 100),    # b_pad=128 boundary of the nibble route
        (100, 3, 4, 8),       # single row chunk, tiny shapes
    ])
    def test_pallas_matches_scatter(self, n, f, L, B):
        # the TPU production path (interpret mode on CPU); masked rows
        # (weight 0), row-chunk accumulation across grid steps, and
        # multi-feature-chunk block indexing must agree with scatter
        rng = np.random.default_rng(2)
        bins = jnp.asarray(rng.integers(0, B, size=(f, n)), jnp.int32)
        grad = jnp.asarray(rng.normal(size=n), jnp.float32)
        hess = jnp.asarray(rng.uniform(0.1, 1, size=n), jnp.float32)
        w = jnp.asarray((rng.random(n) < 0.8), jnp.float32)
        leaf = jnp.asarray(rng.integers(0, L, size=n), jnp.int32)
        h1 = build_histogram(bins, grad, hess, w, leaf, L, B, "scatter")
        h2 = build_histogram(bins, grad, hess, w, leaf, L, B, "pallas")
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=1e-3, atol=1e-3)


class TestPallasTraining:
    """End-to-end training through the Pallas histogram kernel — the
    product path selected by histMethod='auto' on TPU (interpret mode
    here; ref hot loop: TrainUtils.scala:82-89)."""

    def test_train_pallas_matches_scatter(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 5))
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
        kw = {"objective": "binary", "num_iterations": 8, "max_bin": 16,
              "num_leaves": 7, "min_data_in_leaf": 5}
        bp = train({**kw, "hist_method": "pallas"}, X, y)
        bs = train({**kw, "hist_method": "scatter"}, X, y)
        np.testing.assert_allclose(bp.predict(X), bs.predict(X),
                                   rtol=1e-4, atol=1e-4)

    def test_auto_resolves_by_backend(self):
        import jax
        rng = np.random.default_rng(1)
        X = rng.normal(size=(80, 3))
        y = (X[:, 0] > 0).astype(float)
        b = train({"objective": "binary", "num_iterations": 2,
                   "max_bin": 8}, X, y)
        expected = ("pallas" if jax.default_backend() == "tpu"
                    else "scatter")
        assert b.params["hist_method"] == expected

    def test_estimator_accepts_pallas(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(200, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        t = DataTable({"features": X, "label": y})
        m = TPUBoostClassifier(numIterations=5, histMethod="pallas",
                               maxBin=16).fit(t)
        out = m.transform(t)
        assert (out["prediction"] == y).mean() > 0.95


class TestBoosterTraining:
    def test_binary_auc_benchmark_floor(self, breast_cancer):
        # accuracy floor from the reference's benchmark CSV (0.9925 on
        # full data with native LightGBM; we assert a holdout floor)
        X, y = breast_cancer
        rng = np.random.default_rng(0)
        idx = rng.permutation(len(y))
        tr, te = idx[:400], idx[400:]
        b = train({"objective": "binary", "num_iterations": 100}, X[tr], y[tr])
        assert _auc(y[te], b.predict(X[te])) > 0.97

    def test_overfits_train_set(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 50,
                   "min_data_in_leaf": 5}, X, y)
        assert _auc(y, b.predict(X)) > 0.999

    def test_multiclass(self):
        from sklearn.datasets import load_iris
        X, y = load_iris(return_X_y=True)
        b = train({"objective": "multiclass", "num_class": 3,
                   "num_iterations": 30, "min_data_in_leaf": 5}, X, y)
        pred = b.predict(X)
        assert pred.shape == (150, 3)
        np.testing.assert_allclose(pred.sum(axis=1), 1.0, atol=1e-5)
        assert (pred.argmax(1) == y).mean() > 0.95

    def test_regression_r2(self):
        from sklearn.datasets import load_diabetes
        X, y = load_diabetes(return_X_y=True)
        b = train({"objective": "regression", "num_iterations": 100,
                   "min_data_in_leaf": 10}, X, y)
        p = b.predict(X)
        assert 1 - ((p - y) ** 2).mean() / y.var() > 0.9

    def test_quantile_coverage(self):
        from sklearn.datasets import load_diabetes
        X, y = load_diabetes(return_X_y=True)
        b = train({"objective": "quantile", "alpha": 0.9,
                   "num_iterations": 50, "min_data_in_leaf": 10}, X, y)
        cov = (y <= b.predict(X)).mean()
        assert 0.85 < cov < 0.95

    def test_tweedie_positive(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 5))
        y = np.exp(X[:, 0]) * rng.gamma(2.0, 1.0, size=300)
        b = train({"objective": "tweedie", "num_iterations": 30,
                   "min_data_in_leaf": 10}, X, y)
        assert (b.predict(X) > 0).all()

    def test_l1_and_poisson_run(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(200, 3))
        y_l1 = X[:, 0] * 2 + rng.normal(size=200)
        b = train({"objective": "l1", "num_iterations": 20,
                   "min_data_in_leaf": 5}, X, y_l1)
        assert np.isfinite(b.predict(X)).all()
        y_pois = rng.poisson(np.exp(0.5 * X[:, 1]))
        b = train({"objective": "poisson", "num_iterations": 20,
                   "min_data_in_leaf": 5}, X, y_pois.astype(float))
        assert (b.predict(X) > 0).all()

    def test_early_stopping(self, breast_cancer):
        X, y = breast_cancer
        rng = np.random.default_rng(0)
        idx = rng.permutation(len(y))
        tr, te = idx[:350], idx[350:]
        b = train({"objective": "binary", "num_iterations": 500,
                   "early_stopping_round": 10},
                  X[tr], y[tr], valid=(X[te], y[te]))
        assert 0 < b.best_iteration < 500

    def test_max_depth_respected(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 5,
                   "max_depth": 3}, X, y)
        assert max(b.tree_depths) <= 3

    def test_feature_bagging_options(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 20,
                   "feature_fraction": 0.5, "bagging_fraction": 0.7,
                   "bagging_freq": 1}, X, y)
        assert _auc(y, b.predict(X)) > 0.95

    def test_sample_weight_shifts_model(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 2))
        y = (X[:, 0] > 0).astype(float)
        w = np.where(y == 1, 10.0, 1.0)
        b = train({"objective": "binary", "num_iterations": 10}, X, y,
                  sample_weight=w)
        bu = train({"objective": "binary", "num_iterations": 10}, X, y)
        assert b.predict(X).mean() > bu.predict(X).mean()


class TestWarmStart:
    """modelString warm start (ref: TrainUtils.scala:74-77)."""

    def test_warm_start_matches_single_run(self, breast_cancer):
        X, y = breast_cancer
        kw = {"objective": "binary", "num_iterations": 10}
        full = train({**kw, "num_iterations": 20}, X, y)
        first = train(kw, X, y)
        resumed = train(kw, X, y, init_model=first.model_to_string())
        assert resumed.num_trees == 20
        np.testing.assert_allclose(resumed.predict(X), full.predict(X),
                                   rtol=1e-3, atol=1e-3)

    def test_warm_start_different_num_leaves(self, breast_cancer):
        # the continuation may use a different tree size; node dims pad
        X, y = breast_cancer
        first = train({"objective": "binary", "num_iterations": 5,
                       "num_leaves": 7}, X, y)
        resumed = train({"objective": "binary", "num_iterations": 5,
                         "num_leaves": 31}, X, y, init_model=first)
        assert resumed.num_trees == 10
        assert _auc(y, resumed.predict(X)) > _auc(y, first.predict(X))

    def test_estimator_warm_start(self, breast_cancer):
        X, y = breast_cancer
        t = DataTable({"features": np.asarray(X, np.float64),
                       "label": np.asarray(y, np.float64)})
        m1 = TPUBoostClassifier(numIterations=5).fit(t)
        m2 = TPUBoostClassifier(
            numIterations=5,
            initModelString=m1.get("modelString")).fit(t)
        assert m2.get_booster().num_trees == 10

    def test_early_stopped_base_truncated(self, breast_cancer):
        # an early-stopped base contributes only its best_iteration
        # trees to the continuation (raw_score truncates the same way)
        X, y = breast_cancer
        rng = np.random.default_rng(0)
        idx = rng.permutation(len(y))
        tr, te = idx[:350], idx[350:]
        base = train({"objective": "binary", "num_iterations": 200,
                      "early_stopping_round": 5},
                     X[tr], y[tr], valid=(X[te], y[te]))
        assert 0 < base.best_iteration < 200
        resumed = train({"objective": "binary", "num_iterations": 3},
                        X[tr], y[tr], init_model=base)
        assert resumed.num_trees == base.best_iteration + 3

    def test_objective_mismatch_rejected(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "regression", "num_iterations": 2}, X, y)
        with pytest.raises(ValueError, match="link spaces"):
            train({"objective": "binary", "num_iterations": 2}, X, y,
                  init_model=b)

    def test_feature_count_mismatch_rejected(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 2}, X, y)
        with pytest.raises(ValueError, match="features"):
            train({"objective": "binary", "num_iterations": 2},
                  X[:, :3], y, init_model=b)

    def test_class_mismatch_rejected(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 2}, X, y)
        with pytest.raises(ValueError, match="classes"):
            train({"objective": "multiclass", "num_class": 3,
                   "num_iterations": 2}, X[:150],
                  np.arange(150) % 3, init_model=b)


class TestBoostMore:
    """Continued boosting (the incremental-refresh path of the model
    lifecycle): boost_more(data=None) on retained training state is
    BIT-IDENTICAL to one longer run; boost_more(fresh data) appends
    trees against the frozen BinMapper deterministically."""

    # num_leaves/max_bin/hist_method match TestChunkedBoosting's binary
    # config, so the jitted chunk programs come out of _make_chunk_step's
    # lru cache instead of compiling a fresh (leaves, bins) family; all
    # tier-1 iteration counts stay < 16 so only the length-1 chunk
    # program is ever built (chunk-length invariance itself is pinned
    # by TestChunkedBoosting)
    KW = {"objective": "binary", "num_iterations": 8, "num_leaves": 15,
          "max_bin": 31, "hist_method": "scatter", "seed": 3,
          "keep_training_data": True}

    @staticmethod
    def _assert_forests_equal(a, b):
        assert a.num_trees == b.num_trees
        for key in a.trees:
            assert np.array_equal(a.trees[key], b.trees[key]), key
        np.testing.assert_array_equal(a.init_score, b.init_score)

    def test_retained_continuation_bit_identical(self, breast_cancer):
        X, y = breast_cancer
        one_shot = train({**self.KW, "num_iterations": 12}, X, y)
        grown = train(self.KW, X, y).boost_more(4)
        self._assert_forests_equal(one_shot, grown)
        assert grown.train_info["bin_path"] == "retained"

    @pytest.mark.slow   # 3 trains; the single-continuation parity pin
    #                     above is the tier-1 guard
    def test_chained_continuation_bit_identical(self, breast_cancer):
        # two boost_more calls == one longer run; the state moves to
        # the newest booster each time (donated buffers)
        X, y = breast_cancer
        one_shot = train({**self.KW, "num_iterations": 20}, X, y)
        b = train(self.KW, X, y)
        grown = b.boost_more(8).boost_more(4)
        self._assert_forests_equal(one_shot, grown)
        with pytest.raises(ValueError, match="consumed"):
            b.boost_more(1)   # the oldest state is single-use

    @pytest.mark.slow   # heaviest variant (sampling-mask compiles x2);
    #                     mask chunk-invariance is already pinned by
    #                     TestChunkedBoosting, continuation by the
    #                     tier-1 parity pin above
    def test_retained_continuation_with_sampling(self, breast_cancer):
        # bagging + feature-fraction masks key on the ABSOLUTE
        # iteration index (fold_in), so continuation samples exactly
        # the bags one longer run would
        X, y = breast_cancer
        kw = {**self.KW, "bagging_fraction": 0.7, "bagging_freq": 1,
              "feature_fraction": 0.8}
        one_shot = train({**kw, "num_iterations": 12}, X, y)
        grown = train(kw, X, y).boost_more(4)
        self._assert_forests_equal(one_shot, grown)

    def test_retained_state_requires_opt_in(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 4}, X, y)
        with pytest.raises(ValueError, match="keep_training_data"):
            b.boost_more(2)

    def test_fresh_data_frozen_mapper_deterministic(self, breast_cancer):
        X, y = breast_cancer
        base = train(self.KW, X, y)
        rng = np.random.default_rng(7)
        idx = rng.permutation(len(y))[:200]
        X2, y2 = X[idx], y[idx]
        a = base.boost_more(4, X2, y2)
        b = base.boost_more(4, X2, y2)
        assert a.num_trees == base.num_trees + 4
        self._assert_forests_equal(a, b)   # deterministic
        # appended trees split in the base forest's bin space: every
        # new threshold is one of the frozen mapper's cut values
        new_internal = ~a.trees["is_leaf"][base.num_trees:].astype(bool)
        thr = a.trees["threshold"][base.num_trees:][new_internal]
        feats = a.trees["feature"][base.num_trees:][new_internal]
        lut = base.bin_mapper.threshold_matrix(
            int(base.bin_mapper.num_bins.max()))
        for t, f in zip(thr, feats):
            assert np.isin(t, lut[f]).item() or not np.isfinite(t), (t, f)

    @pytest.mark.slow   # quality smoke; determinism + frozen-mapper
    #                     structure above are the tier-1 contract
    def test_fresh_data_improves_fit(self, breast_cancer):
        X, y = breast_cancer
        base = train({**self.KW, "num_iterations": 5}, X, y)
        grown = base.boost_more(10, X, y)
        assert _auc(y, grown.predict(X)) >= _auc(y, base.predict(X))

    def test_deserialized_booster_rejects_fresh_data(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 3}, X, y)
        loaded = Booster.from_string(b.model_to_string())
        with pytest.raises(ValueError, match="BinMapper"):
            loaded.boost_more(2, X, y)

    def test_estimator_keep_training_data_param(self, breast_cancer):
        X, y = breast_cancer
        t = DataTable({"features": np.asarray(X, np.float64),
                       "label": np.asarray(y, np.float64)})
        m = TPUBoostClassifier(numIterations=4,
                               keepTrainingData=True).fit(t)
        grown = m.get_booster().boost_more(2)
        assert grown.num_trees == 6


class TestStreamingIngestion:
    def test_shard_stream_matches_dense(self, breast_cancer):
        # iterator-of-shards feed: only the binned int32 matrix is kept
        # (bin boundaries fitted on the first shard's sample)
        X, y = breast_cancer
        kw = {"objective": "binary", "num_iterations": 20}
        b_dense = train(kw, X, y)

        def shards():
            for lo in range(0, len(y), 150):
                yield X[lo:lo + 150], y[lo:lo + 150]

        b_stream = train(kw, shards())
        # first-shard binning differs slightly from full-data binning;
        # the model must still be equivalent in quality
        assert _auc(y, b_stream.predict(X)) > 0.99
        assert abs(_auc(y, b_dense.predict(X))
                   - _auc(y, b_stream.predict(X))) < 0.005

    def test_shard_stream_with_weights(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 3))
        y = (X[:, 0] > 0).astype(float)
        w = np.where(y == 1, 5.0, 1.0)
        b = train({"objective": "binary", "num_iterations": 10},
                  [(X[:200], y[:200], w[:200]), (X[200:], y[200:], w[200:])])
        bu = train({"objective": "binary", "num_iterations": 10}, X, y)
        assert b.predict(X).mean() > bu.predict(X).mean()

    def test_empty_stream_raises(self):
        with pytest.raises(ValueError, match="empty shard stream"):
            train({"objective": "binary"}, iter([]))


class TestEdgeCases:
    def test_nan_routing_consistent_train_predict(self):
        # NaN maps to bin 0 (left) in training; inference must agree
        rng = np.random.default_rng(0)
        n = 300
        X = rng.normal(size=(n, 3))
        X[:60, 0] = np.nan
        y = ((np.nan_to_num(X[:, 0], nan=-5.0) > 0)).astype(float)
        b = train({"objective": "binary", "num_iterations": 20,
                   "min_data_in_leaf": 5}, X, y)
        p = b.predict(X)
        # NaN rows are all label 0; a consistent model predicts them low
        assert p[:60].max() < 0.5
        from sklearn.metrics import roc_auc_score
        assert roc_auc_score(y, p) > 0.99

    def test_unsplittable_data_predicts_base_score(self):
        # no split possible (n < 2*min_data_in_leaf): trees are single
        # leaves; prediction must still reflect accumulated leaf values
        rng = np.random.default_rng(1)
        X = rng.normal(size=(30, 2))
        y = np.full(30, 5.17)
        b = train({"objective": "regression", "num_iterations": 50,
                   "min_data_in_leaf": 20, "boost_from_average": False},
                  X, y)
        # single-leaf trees converge geometrically: 5.17*(1-0.9^50)
        np.testing.assert_allclose(b.predict(X), np.full(30, 5.17),
                                   rtol=0.02)

    def test_constant_features_no_crash(self):
        X = np.ones((100, 3))
        y = np.random.default_rng(0).random(100)
        b = train({"objective": "regression", "num_iterations": 3}, X, y)
        assert np.isfinite(b.predict(X)).all()


class TestBoosterSerialization:
    def test_string_roundtrip(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 10}, X, y)
        b2 = Booster.from_string(b.model_to_string())
        np.testing.assert_allclose(b.predict(X), b2.predict(X), atol=1e-6)

    def test_save_native_model(self, breast_cancer, tmp_path):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 5}, X, y)
        p = str(tmp_path / "model.txt")
        b.save_native_model(p)
        b2 = Booster.load_native_model(p)
        np.testing.assert_allclose(b.predict(X), b2.predict(X), atol=1e-6)

    def test_feature_importance(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 10}, X, y)
        fi = b.feature_importance("split")
        assert fi.shape == (X.shape[1],) and fi.sum() > 0
        fg = b.feature_importance("gain")
        assert (fg >= 0).all() and fg.sum() > 0


class TestDataParallel:
    """shard_map + psum'd histograms over the 8-device mesh — the analog
    of the reference's partitions-as-nodes local test
    (ref: LightGBMUtils.scala:235-249 getNodesFromPartitionsLocal)."""

    def test_dp_matches_serial(self, cpu_mesh_devices):
        from sklearn.datasets import load_diabetes
        X, y = load_diabetes(return_X_y=True)
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "regression", "num_iterations": 15,
              "min_data_in_leaf": 10}
        bd = train({**kw, "parallelism": "data"}, X, y, mesh=mesh)
        bs = train(kw, X, y)
        np.testing.assert_allclose(bd.predict(X), bs.predict(X),
                                   rtol=1e-3, atol=1e-3)

    def test_dp_binary(self, breast_cancer, cpu_mesh_devices):
        X, y = breast_cancer
        mesh = mesh_lib.make_mesh()
        b = train({"objective": "binary", "num_iterations": 20,
                   "parallelism": "data"}, X, y, mesh=mesh)
        assert _auc(y, b.predict(X)) > 0.99


class TestEstimatorStages:
    def _classification_table(self, X, y):
        return DataTable({"features": np.asarray(X, dtype=np.float64),
                          "label": np.asarray(y, dtype=np.float64)})

    def test_classifier_fit_transform(self, breast_cancer):
        X, y = breast_cancer
        t = self._classification_table(X, y)
        clf = TPUBoostClassifier(numIterations=20)
        model = clf.fit(t)
        out = model.transform(t)
        assert {"rawPrediction", "probability", "prediction"} <= \
            set(out.column_names)
        prob = out["probability"]
        assert prob.shape == (len(y), 2)
        acc = (out["prediction"] == y).mean()
        assert acc > 0.97

    def test_classifier_save_load(self, breast_cancer, tmp_path):
        X, y = breast_cancer
        t = self._classification_table(X[:200], y[:200])
        model = TPUBoostClassifier(numIterations=5).fit(t)
        path = str(tmp_path / "clf_model")
        model.save(path)
        from mmlspark_tpu.gbdt import TPUBoostClassificationModel
        m2 = TPUBoostClassificationModel.load(path)
        np.testing.assert_allclose(m2.transform(t)["probability"],
                                   model.transform(t)["probability"],
                                   atol=1e-6)

    def test_regressor_stage(self):
        from sklearn.datasets import load_diabetes
        X, y = load_diabetes(return_X_y=True)
        t = DataTable({"features": X, "label": y})
        model = TPUBoostRegressor(numIterations=100, minDataInLeaf=10).fit(t)
        out = model.transform(t)
        p = out["prediction"]
        assert 1 - ((p - y) ** 2).mean() / y.var() > 0.8

    def test_rejects_unindexed_labels(self):
        X = np.random.default_rng(0).normal(size=(50, 2))
        t = DataTable({"features": X,
                       "label": np.where(X[:, 0] > 0, 5.0, 7.0)})
        with pytest.raises(ValueError, match="0..K-1"):
            TPUBoostClassifier(numIterations=2).fit(t)

    def test_schema_propagation(self, breast_cancer):
        X, y = breast_cancer
        t = self._classification_table(X[:50], y[:50])
        clf = TPUBoostClassifier(numIterations=2)
        out_schema = clf.transform_schema(t.schema)
        assert "probability" in out_schema.names
        assert "prediction" in out_schema.names


class TestLargeBinCounts:
    def test_huge_max_bin_routes_to_onehot(self):
        # VMEM tiling can't hold >2048 bins; 'pallas' must degrade to
        # onehot instead of failing Mosaic allocation on TPU
        rng = np.random.default_rng(0)
        X = rng.normal(size=(3000, 2))
        y = (X[:, 0] > 0).astype(float)
        b = train({"objective": "binary", "num_iterations": 3,
                   "max_bin": 4095, "hist_method": "pallas"}, X, y)
        assert b.params["hist_method"] == "onehot"
        assert np.isfinite(b.predict(X)).all()


class TestFeatureParallel:
    """tree_learner='feature': feature-axis sharding, all_gather'd split
    candidates, owner-broadcast row partitions
    (ref: TrainParams.scala:26 tree_learner=feature)."""

    def test_fp_identical_to_serial(self, cpu_mesh_devices):
        rng = np.random.default_rng(0)
        n, f = 2000, 37          # F not divisible by 8 -> exercises padding
        X = rng.normal(size=(n, f))
        y = (X[:, 0] * 2 + X[:, 1] * X[:, 2] > 0).astype(float)
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "binary", "num_iterations": 6,
              "num_leaves": 15, "max_bin": 31, "min_data_in_leaf": 5}
        bs = train(kw, X, y)
        bf = train({**kw, "parallelism": "feature"}, X, y, mesh=mesh)
        # rows are replicated, decisions exchanged exactly -> identical
        for k in ("feature", "bin_threshold", "left", "right"):
            np.testing.assert_array_equal(bs.trees[k], bf.trees[k])
        np.testing.assert_allclose(bs.predict(X), bf.predict(X),
                                   rtol=1e-5, atol=1e-6)

    def test_fp_with_sampling_and_esr(self, cpu_mesh_devices):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(1200, 24))
        y = X[:, 0] * 3 + np.sin(X[:, 1]) + rng.normal(
            scale=0.1, size=1200)
        mesh = mesh_lib.make_mesh()
        b = train({"objective": "regression", "num_iterations": 30,
                   "num_leaves": 15, "parallelism": "feature",
                   "feature_fraction": 0.7, "bagging_fraction": 0.8,
                   "bagging_freq": 1, "early_stopping_round": 5},
                  X[:1000], y[:1000], mesh=mesh,
                  valid=(X[1000:], y[1000:]))
        pred = b.predict(X[1000:])
        ss_res = np.sum((pred - y[1000:]) ** 2)
        ss_tot = np.sum((y[1000:] - y[1000:].mean()) ** 2)
        assert 1 - ss_res / ss_tot > 0.8

    def test_fp_estimator_stage(self, cpu_mesh_devices):
        from mmlspark_tpu.gbdt.estimators import TPUBoostClassifier
        from mmlspark_tpu.core.table import DataTable
        rng = np.random.default_rng(2)
        X = rng.normal(size=(600, 12))
        y = (X[:, 0] + X[:, 3] > 0).astype(np.int64)
        t = DataTable({"features": X.astype(np.float32), "label": y})
        clf = TPUBoostClassifier(numIterations=8, numLeaves=15,
                                 parallelism="feature", labelCol="label")
        model = clf.fit(t)
        out = model.transform(t)
        acc = np.mean(np.asarray(out["prediction"]) == y)
        assert acc > 0.9


class TestVotingParallel:
    """tree_learner='voting': PV-tree scheme — rows sharded like 'data',
    but only the union of each worker's top-k locally-ranked features
    allreduces per split (ref: TrainParams.scala:26 tree_learner=voting).
    """

    def _data(self, n=2400, f=24, seed=3):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, f))
        y = (X[:, 0] * 2 + X[:, 1] * X[:, 2] + 0.5 * X[:, 5] > 0
             ).astype(float)
        return X, y

    def test_voting_identical_to_data_parallel_when_k_covers_f(
            self, cpu_mesh_devices):
        """voting_k >= F: every worker votes every feature, so the
        candidate union covers F and the split SEARCH equals the
        data-parallel learner's. Guarantees tested:

        - every tree's ROOT split matches data-parallel bitwise (the
          root histogram is psum'd directly, no subtraction cache);
        - deeper nodes agree except where f32 reassociation of the
          sibling-subtraction cache (local-subtract-then-psum vs
          psum-then-subtract; gain deltas ~1e-6 relative) flips a
          near-tie — bounded to a few nodes per forest;
        - predictions agree with serial within float tolerance."""
        X, y = self._data()
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "binary", "num_iterations": 6,
              "num_leaves": 15, "max_bin": 31, "min_data_in_leaf": 5,
              "hist_method": "scatter"}
        bs = train(kw, X, y)
        bd = train({**kw, "parallelism": "data"}, X, y, mesh=mesh)
        bv = train({**kw, "parallelism": "voting", "top_k": X.shape[1]},
                   X, y, mesh=mesh)
        # root splits: bitwise
        np.testing.assert_array_equal(bd.trees["feature"][:, 0],
                                      bv.trees["feature"][:, 0])
        np.testing.assert_array_equal(bd.trees["bin_threshold"][:, 0],
                                      bv.trees["bin_threshold"][:, 0])
        # full structure: near-tie flips only
        total = mismatched = 0
        for k in ("feature", "bin_threshold", "left", "right"):
            total += bd.trees[k].size
            mismatched += int(np.sum(bd.trees[k] != bv.trees[k]))
        assert mismatched <= 0.02 * total, \
            f"{mismatched}/{total} nodes diverged (expected near-ties only)"
        np.testing.assert_allclose(bs.predict(X), bv.predict(X),
                                   rtol=5e-2, atol=5e-3)

    def test_voting_quality_at_small_k(self, cpu_mesh_devices):
        """top_k < F: approximate split search — the model may differ
        from serial but must stay predictive (PV-tree's accuracy claim)."""
        X, y = self._data()
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "binary", "num_iterations": 20,
              "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
              "hist_method": "scatter"}
        bv = train({**kw, "parallelism": "voting", "top_k": 3},
                   X, y, mesh=mesh)
        assert _auc(y, bv.predict(X)) > 0.95

    def test_voting_collective_is_candidate_sized(self, cpu_mesh_devices):
        """The point of PV-tree: the per-split histogram allreduce moves
        O(devices*k*B) candidate slices, never the full (3, F, B)
        histogram. Assert on the traced jaxpr of the voting step: every
        histogram-shaped psum is candidate-width, and the full-F width
        appears in no psum."""
        import re
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from mmlspark_tpu.gbdt.tree import GrowParams, grow_tree

        f, n, b, k = 40, 512, 16, 4
        mesh = mesh_lib.make_mesh()
        n_dev = mesh.shape[mesh_lib.DATA_AXIS]
        gp = GrowParams(num_leaves=7, num_bins=b, min_data_in_leaf=5,
                        hist_method="scatter", voting_k=k)

        def run(bins, g, h, w, fm):
            return grow_tree(bins, g, h, w, fm, gp,
                             mesh_lib.DATA_AXIS, "voting")[1]

        mapped = shard_map(
            run, mesh=mesh,
            in_specs=(P(None, "data"), P("data"), P("data"), P("data"),
                      P(None)),
            out_specs=P("data"), check_vma=False)
        args = (jnp.zeros((f, n), jnp.int32), jnp.zeros(n), jnp.zeros(n),
                jnp.ones(n), jnp.ones(f))
        txt = str(jax.make_jaxpr(mapped)(*args))
        # each psum eqn's OUTPUT aval leads its line ("x:f32[3,33,16] =
        # psum["); histogram-shaped ones end [..., W, b] — collect W
        widths = set()
        for m in re.finditer(rf"f32\[(?:\d+,)*(\d+),{b}\]\s*=\s*psum",
                             txt):
            widths.add(int(m.group(1)))
        cand_w = n_dev * k + 1    # voted slices + the feature-0 totals row
        assert widths and max(widths) <= cand_w, \
            f"psum widths {sorted(widths)} exceed candidate size " \
            f"{cand_w} (full F={f} would mean the PV-tree saving is gone)"


class TestStreamBinFidelity:
    """Reservoir sampling across all shards before fixing bin boundaries
    (ref: LightGBM BinMapper samples the whole dataset, not the head)."""

    def _skewed_shards(self, n=6000, seed=0):
        """Shards SORTED by the informative feature — the adversarial
        order where first-shard binning collapses."""
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 5))
        X[:, 0] = rng.exponential(scale=4.0, size=n)   # heavy tail
        y = (X[:, 0] > np.median(X[:, 0])).astype(float)
        order = np.argsort(X[:, 0])                    # worst case
        X, y = X[order], y[order]
        return [(X[i:i + 1000], y[i:i + 1000]) for i in range(0, n, 1000)], X, y

    def test_replayable_stream_matches_dense_quality(self):
        shards, X, y = self._skewed_shards()
        kw = {"objective": "binary", "num_iterations": 15,
              "num_leaves": 15, "max_bin": 31, "min_data_in_leaf": 5,
              "hist_method": "scatter"}
        b_dense = train(kw, X, y)
        b_stream = train(kw, shards)       # replayable list -> two-pass
        a_d = _auc(y, b_dense.predict(X))
        a_s = _auc(y, b_stream.predict(X))
        assert a_s > 0.99
        assert abs(a_d - a_s) < 0.005, (a_d, a_s)

    def test_factory_stream_two_pass(self):
        shards, X, y = self._skewed_shards(seed=1)
        b = train({"objective": "binary", "num_iterations": 10,
                   "num_leaves": 15, "min_data_in_leaf": 5,
                   "hist_method": "scatter"}, lambda: iter(shards))
        assert _auc(y, b.predict(X)) > 0.99

    def test_oneshot_skewed_stream_warns(self):
        import logging
        shards, X, y = self._skewed_shards(seed=2)
        records = []
        handler = logging.Handler()
        handler.emit = records.append   # the pkg logger doesn't propagate
        lg = logging.getLogger("mmlspark_tpu.gbdt")
        lg.addHandler(handler)
        try:
            train({"objective": "binary", "num_iterations": 5,
                   "num_leaves": 7, "hist_method": "scatter",
                   "min_data_in_leaf": 5}, iter(shards))
        finally:
            lg.removeHandler(handler)
        assert any("binning drift" in r.getMessage() for r in records)


class TestDeviceBinning:
    """On-device bucketize (raw f32 blocks + jitted searchsorted) must
    be a pure performance change: bit-identical bins to the host
    BinMapper.transform whenever f32_safe() certifies the mapper, and a
    clean fallback to host binning everywhere else."""

    def _adversarial_f32(self, n=20_000, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 6)).astype(np.float32)
        X[::7, 0] = np.nan
        X[::11, 1] = np.inf
        X[::13, 2] = -np.inf
        X[:, 3] = np.round(X[:, 3])          # heavy repeats
        X[:, 4] = 2.0                        # constant feature
        return X

    def test_device_bins_bit_identical(self):
        from mmlspark_tpu.gbdt.binning import bucketize_fm_device
        X = self._adversarial_f32()
        m = BinMapper.fit(X, max_bin=63)
        # f32 input -> f32-snapped cuts -> f32-safe by construction
        assert m.f32_safe()
        host = m.transform(X)
        dev = np.asarray(bucketize_fm_device(
            jnp.asarray(X), jnp.asarray(m.bounds_matrix())))
        np.testing.assert_array_equal(host.T, dev)

    def test_device_bins_bit_identical_at_full_bin_width(self):
        from mmlspark_tpu.gbdt.binning import bucketize_fm_device
        rng = np.random.default_rng(3)
        X = rng.normal(size=(50_000, 4)).astype(np.float32)
        m = BinMapper.fit(X, max_bin=255)
        assert m.f32_safe()
        dev = np.asarray(bucketize_fm_device(
            jnp.asarray(X), jnp.asarray(m.bounds_matrix())))
        np.testing.assert_array_equal(m.transform(X).T, dev)

    def test_f64_input_stays_on_host_even_when_f32_safe(self):
        # float64 input can be f32-safe for INFERENCE (gap margin +
        # holdout certify the sample) yet the certification is
        # probabilistic for unsampled rows — training must not let the
        # ingest path change the forest, so device binning requires
        # f32-EXACT cuts (float32 input)
        rng = np.random.default_rng(6)
        X = rng.normal(size=(800, 4))            # float64
        y = (X[:, 0] > 0).astype(float)
        m = BinMapper.fit(X, max_bin=16)
        assert m.f32_safe() and not m.f32_cuts_exact
        b = train({"objective": "binary", "num_iterations": 3,
                   "hist_method": "scatter"}, X, y)
        assert b.train_info["bin_path"] == "host"

    def test_f32_unsafe_mapper_stays_on_host(self):
        # f64 timestamp-scale cuts cannot run in f32; train must record
        # the host ingest path and keep full split resolution
        rng = np.random.default_rng(1)
        ts = (1.7e9 + rng.integers(0, 600, size=2000)).astype(float)
        y = (ts % 600 > 300).astype(float)
        b = train({"objective": "binary", "num_iterations": 20,
                   "min_data_in_leaf": 5}, ts[:, None], y)
        assert b.train_info["bin_path"] == "host"
        assert _auc(y, b.predict(ts[:, None])) > 0.99

    @pytest.mark.slow   # end-to-end train x2; bin parity above is the
    def test_device_vs_host_forest_identical(self):   # tier-1 guard
        rng = np.random.default_rng(2)
        X = rng.normal(size=(12_000, 9)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(float)
        kw = {"objective": "binary", "num_iterations": 10,
              "num_leaves": 15, "max_bin": 63, "hist_method": "scatter"}
        bd = train(dict(kw), X, y)
        bh = train(dict(kw, device_binning="off"), X, y)
        assert bd.train_info["bin_path"] == "device"
        assert bh.train_info["bin_path"] == "host"
        for k in bd.trees:
            np.testing.assert_array_equal(bd.trees[k], bh.trees[k])
        np.testing.assert_array_equal(bd.predict(X), bh.predict(X))
        # device path records its own kernel phase; host path never does
        assert "bin_device" in bd.train_timing
        assert "bin_device" not in bh.train_timing

    def test_forced_on_falls_back_for_csr(self):
        # CSR ingest cannot ship raw float blocks; 'on' warns + host path
        import logging
        from mmlspark_tpu.core.sparse import CSRMatrix
        rng = np.random.default_rng(4)
        X = rng.normal(size=(500, 5)).astype(np.float32)
        X[rng.random(X.shape) < 0.6] = 0.0
        y = (X[:, 0] > 0).astype(float)
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        lg = logging.getLogger("mmlspark_tpu.gbdt")
        lg.addHandler(handler)
        try:
            b = train({"objective": "binary", "num_iterations": 3,
                       "device_binning": "on", "hist_method": "scatter"},
                      CSRMatrix.from_dense(X), y)
        finally:
            lg.removeHandler(handler)
        assert b.train_info["bin_path"] == "host"
        assert any("device_binning" in r.getMessage() for r in records)

    def test_threaded_host_binning_parity(self):
        # the host fallback's feature-block thread pool must be
        # invisible: identical bins at any worker count
        X = np.asarray(self._adversarial_f32(5000), np.float64)
        X[0, 0] = 1.7e9   # keep it f32-unsafe so host is the real path
        X[1, 0] = 1.7e9 + 1
        m = BinMapper.fit(X, max_bin=31)
        one = m._numpy_bin_block(X, 0, X.shape[1], workers=1)
        many = m._numpy_bin_block(X, 0, X.shape[1], workers=4)
        np.testing.assert_array_equal(one, many)
        np.testing.assert_array_equal(one, m.transform(X).T)
        np.testing.assert_array_equal(one[2:5],
                                      m.transform_fm_range(X, 2, 5))


class TestChunkedBoosting:
    """Iteration-batched boosting (boost_chunk iterations fused into one
    lax.scan dispatch) must be a pure performance change: with a fixed
    seed the forest is bit-identical to the per-iteration loop
    (boost_chunk=1), including with bagging, feature_fraction, and
    early stopping enabled."""

    def _assert_same_forest(self, a, b):
        assert set(a.trees) == set(b.trees)
        for k in a.trees:
            np.testing.assert_array_equal(a.trees[k], b.trees[k], err_msg=k)

    @pytest.mark.slow   # the esr+sampling variant below is the tier-1
    def test_chunked_forest_identical(self):          # parity guard
        rng = np.random.default_rng(0)
        X = rng.normal(size=(3000, 6)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
        kw = {"objective": "binary", "num_iterations": 20,
              "num_leaves": 15, "max_bin": 31, "hist_method": "scatter"}
        b8 = train(dict(kw, boost_chunk=8), X, y)
        b1 = train(dict(kw, boost_chunk=1), X, y)
        assert b8.train_info["boost_chunk"] == 8
        assert b8.train_info["boost_chunks"] == 3    # 8 + 8 + 4
        assert b1.train_info["boost_chunks"] == 20
        self._assert_same_forest(b8, b1)

    def test_chunked_with_sampling_and_esr_identical(self):
        # device-derived masks are a pure function of (seed, iteration),
        # so chunking cannot change them; esr segments chunks at
        # esr_sync boundaries so both paths stop at the same read point
        rng = np.random.default_rng(1)
        X = rng.normal(size=(1500, 8)).astype(np.float32)
        y = X[:, 0] * 2 + rng.normal(scale=0.3, size=1500)
        kw = {"objective": "regression", "num_iterations": 200,
              "num_leaves": 7, "learning_rate": 0.3,
              "early_stopping_round": 5, "hist_method": "scatter",
              "min_data_in_leaf": 5, "bagging_fraction": 0.8,
              "bagging_freq": 2, "feature_fraction": 0.7, "seed": 11}
        valid = (X[1200:], y[1200:])
        b8 = train(dict(kw, boost_chunk=8), X[:1200], y[:1200],
                   valid=valid)
        b1 = train(dict(kw, boost_chunk=1), X[:1200], y[:1200],
                   valid=valid)
        assert 0 < b8.best_iteration < 200   # esr actually fired
        assert b8.best_iteration == b1.best_iteration
        assert b8.num_trees == b1.num_trees
        self._assert_same_forest(b8, b1)

    @pytest.mark.slow   # parity extra beyond the tier-1 chunk suite
    def test_multiclass_chunked_identical(self):
        from sklearn.datasets import load_iris
        X, y = load_iris(return_X_y=True)
        kw = {"objective": "multiclass", "num_class": 3,
              "num_iterations": 18, "min_data_in_leaf": 5,
              "hist_method": "scatter"}
        b8 = train(dict(kw, boost_chunk=8), X, y)
        b1 = train(dict(kw, boost_chunk=1), X, y)
        self._assert_same_forest(b8, b1)
        assert (b8.predict(X).argmax(1) == y).mean() > 0.95

    @pytest.mark.slow   # 8-device mesh compile dominates (~20s wall)
    def test_dp_sampling_masks_match_serial(self, cpu_mesh_devices):
        # data-parallel derives the SAME global bag as serial: the
        # per-row uniforms are counter-based (key, global row id), so
        # they are invariant to shard layout AND row padding — N is
        # deliberately NOT divisible by the 8-device mesh, the case
        # where a length-dependent uniform stream would diverge.
        # Forests agree up to the psum reassociation tolerance the
        # plain dp-vs-serial test already accepts.
        n = 2001
        rng = np.random.default_rng(5)
        X = rng.normal(size=(n, 10)).astype(np.float32)
        y = X[:, 0] * 2 + np.sin(X[:, 1]) + rng.normal(
            scale=0.1, size=n)
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "regression", "num_iterations": 10,
              "num_leaves": 15, "min_data_in_leaf": 10,
              "bagging_fraction": 0.7, "bagging_freq": 1,
              "feature_fraction": 0.8, "seed": 5,
              "hist_method": "scatter", "boost_chunk": 4}
        bs = train(dict(kw), X, y)
        bd = train(dict(kw, parallelism="data"), X, y, mesh=mesh)
        np.testing.assert_allclose(bd.predict(X), bs.predict(X),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.slow   # retrace guard also enforced by the perf floor
    def test_seed_sweep_does_not_retrace_chunks(self):
        # the mask key is a runtime input to the chunk program: a seed
        # sweep with bagging active (CV folds, bagged ensembles) must
        # reuse the compiled executable, not recompile per seed
        from mmlspark_tpu.gbdt import booster as booster_mod
        rng = np.random.default_rng(7)
        X = rng.normal(size=(600, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(float)
        kw = {"objective": "binary", "num_iterations": 8,
              "num_leaves": 7, "boost_chunk": 4, "max_bin": 31,
              "bagging_fraction": 0.8, "bagging_freq": 1,
              "feature_fraction": 0.8, "hist_method": "scatter",
              "min_data_in_leaf": 5}
        b1 = train(dict(kw, seed=1), X, y)
        before = dict(booster_mod.trace_counts())
        b2 = train(dict(kw, seed=2), X, y)
        delta = {k: v - before.get(k, 0)
                 for k, v in booster_mod.trace_counts().items()
                 if v != before.get(k, 0)}
        assert not delta, f"seed change retraced: {delta}"
        # and the seed still matters: different bags -> different forest
        assert any(not np.array_equal(b1.trees[k], b2.trees[k])
                   for k in b1.trees)

    def test_ff_zero_still_honors_seed(self):
        # feature_fraction=0.0 is falsy but DOES sample masks
        # (max(1, ceil(0*F)) = 1 feature per tree): the mask key must
        # still come from the user's seed, not the pinned no-mask key
        rng = np.random.default_rng(3)
        X = rng.normal(size=(400, 8)).astype(np.float32)
        y = X[:, 0] - X[:, 3] + 0.1 * rng.normal(size=400)
        kw = {"objective": "regression", "num_iterations": 6,
              "num_leaves": 7, "max_bin": 31, "hist_method": "scatter",
              "min_data_in_leaf": 5, "feature_fraction": 0.0}
        b1 = train(dict(kw, seed=1), X, y)
        b2 = train(dict(kw, seed=2), X, y)
        assert any(not np.array_equal(b1.trees[k], b2.trees[k])
                   for k in b1.trees)

    def test_estimator_boost_chunk_passthrough(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(400, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        t = DataTable({"features": X, "label": y})
        m = TPUBoostClassifier(numIterations=16, boostChunk=4,
                               histMethod="scatter").fit(t)
        b = m.get_booster()
        assert b.params["boost_chunk"] == 4
        out = m.transform(t)
        assert (out["prediction"] == y).mean() > 0.9


class TestDeviceForestCache:
    def test_predict_reuses_device_trees(self, breast_cancer):
        X, y = breast_cancer
        b = train({"objective": "binary", "num_iterations": 6}, X, y)
        if b._needs_f64_inference():
            pytest.skip("f64 host inference path — no device cache")
        p1 = b.predict(X)
        cache = b._dev_forest
        assert cache is not None
        p2 = b.predict(X)
        assert b._dev_forest is cache        # same upload reused
        np.testing.assert_array_equal(p1, p2)
        # t_limit change invalidates (num_iteration truncation)
        b.predict(X, num_iteration=2)
        assert b._dev_forest is not cache
        assert b._dev_forest[0] == 2 * b.num_class


class TestAsyncEarlyStopping:
    def test_esr_still_stops_and_best_iter_exact(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(1500, 8))
        y = X[:, 0] * 2 + rng.normal(scale=0.3, size=1500)
        kw = {"objective": "regression", "num_iterations": 200,
              "num_leaves": 7, "learning_rate": 0.3,
              "early_stopping_round": 5, "hist_method": "scatter",
              "min_data_in_leaf": 5}
        b = train(kw, X[:1200], y[:1200], valid=(X[1200:], y[1200:]))
        # overfits quickly at lr=0.3 -> must stop well before 200
        assert 0 < b.best_iteration < 150
        # at most esr_sync-1 extra trees trained past the stop point
        assert b.num_trees <= b.best_iteration + 5 + 8


class TestPipelinedShip:
    """Chunked bin+ship overlap (host bins feature chunk j while chunk
    j-1's transfer is in flight) must be a pure performance change:
    identical forest, phases still attributed."""

    @staticmethod
    def _require_range_kernel():
        from mmlspark_tpu.native import loader as native
        if native.get_lib() is None:
            pytest.skip("native range kernel unavailable — the "
                        "pipelined path cannot engage (serial==serial "
                        "would pass vacuously)")

    def test_pipelined_forest_identical(self):
        import json
        self._require_range_kernel()
        rng = np.random.default_rng(3)
        X = rng.normal(size=(20_000, 12)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
        base = {"objective": "binary", "num_iterations": 8,
                "num_leaves": 15, "max_bin": 63}
        serial = train(dict(base), X, y)
        # tiny chunk budget forces 3-feature chunks -> 4 chunks
        piped = train(dict(base, ship_chunk_bytes=20_000 * 3), X, y)
        ts = json.loads(serial.model_to_string())["trees"]
        tp = json.loads(piped.model_to_string())["trees"]
        assert ts == tp
        np.testing.assert_array_equal(serial.predict(X), piped.predict(X))
        for key in ("bin", "ship", "first_iter", "boost", "fetch"):
            assert key in piped.train_timing, piped.train_timing

    def test_pipelined_with_feature_pad_and_mesh(self, cpu_mesh_devices):
        """Data-parallel mesh + row padding + forced chunking: the
        sharded placement consumes the device-concatenated bins."""
        import json
        self._require_range_kernel()
        rng = np.random.default_rng(4)
        X = rng.normal(size=(10_001, 7)).astype(np.float32)  # pad rows
        y = (X[:, 0] > 0).astype(float)
        base = {"objective": "binary", "num_iterations": 5,
                "num_leaves": 7, "max_bin": 31, "parallelism": "data",
                "hist_method": "scatter"}
        serial = train(dict(base), X, y)
        piped = train(dict(base, ship_chunk_bytes=10_001 * 2), X, y)
        assert json.loads(serial.model_to_string())["trees"] == \
            json.loads(piped.model_to_string())["trees"]
