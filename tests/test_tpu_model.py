import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.models.tpu_model import TPUModel
from mmlspark_tpu.parallel import mesh as mesh_lib


class TinyMLP(nn.Module):
    features: int = 8
    out: int = 3

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.features)(x)
        x = nn.relu(x)
        return nn.Dense(self.out)(x)


def _make_model(in_dim=4, batch_size=16):
    module = TinyMLP()
    params = module.init(jax.random.PRNGKey(0), jnp.ones((1, in_dim)))
    model = TPUModel.from_flax(module, params,
                               inputCol="features", outputCol="scores",
                               batchSize=batch_size)
    return module, params, model


def test_basic_inference():
    module, params, model = _make_model()
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(10, 4)).astype(np.float32)
    t = DataTable({"features": feats})
    out = model.transform(t)
    assert out["scores"].shape == (10, 3)
    expected = np.asarray(module.apply(params, jnp.asarray(feats)))
    np.testing.assert_allclose(out["scores"], expected, rtol=1e-4, atol=1e-4)


def test_batching_padding_correct():
    # 10 rows with batch 4 and an 8-device mesh: padding paths exercised
    module, params, model = _make_model(batch_size=4)
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(10, 4)).astype(np.float32)
    t = DataTable({"features": feats})
    out = model.transform(t)
    expected = np.asarray(module.apply(params, jnp.asarray(feats)))
    np.testing.assert_allclose(out["scores"], expected, rtol=1e-4, atol=1e-4)


def test_sharded_over_mesh():
    module, params, model = _make_model()
    model.set_mesh(mesh_lib.make_mesh({"data": 8}))
    rng = np.random.default_rng(2)
    feats = rng.normal(size=(32, 4)).astype(np.float32)
    out = model.transform(DataTable({"features": feats}))
    expected = np.asarray(module.apply(params, jnp.asarray(feats)))
    np.testing.assert_allclose(out["scores"], expected, rtol=1e-4, atol=1e-4)


def test_feed_fetch_dicts():
    class TwoHead(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(4)(x)
            return {"a": nn.Dense(2)(h), "b": nn.Dense(5)(h)}

    module = TwoHead()
    params = module.init(jax.random.PRNGKey(0), jnp.ones((1, 3)))
    model = TPUModel.from_flax(
        module, params,
        feedDict={"x": "feats"},
        fetchDict={"out_a": "a", "out_b": "b"})
    feats = np.random.default_rng(0).normal(size=(6, 3)).astype(np.float32)
    out = model.transform(DataTable({"feats": feats}))
    assert out["out_a"].shape == (6, 2)
    assert out["out_b"].shape == (6, 5)


def test_bfloat16_path():
    module, params, model = _make_model()
    model.set("computeDtype", "bfloat16")
    feats = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)
    out = model.transform(DataTable({"features": feats}))
    expected = np.asarray(module.apply(params, jnp.asarray(feats)))
    np.testing.assert_allclose(out["scores"], expected, rtol=0.05, atol=0.05)


def test_vector_list_column():
    module, params, model = _make_model()
    rng = np.random.default_rng(3)
    feats = [rng.normal(size=4) for _ in range(5)]
    t = DataTable({"features": feats})
    out = model.transform(t)
    assert out["scores"].shape == (5, 3)


def test_save_load_roundtrip(tmp_path):
    module, params, model = _make_model()
    feats = np.random.default_rng(4).normal(size=(6, 4)).astype(np.float32)
    t = DataTable({"features": feats})
    out1 = model.transform(t)

    p = str(tmp_path / "model")
    model.save(p)
    from mmlspark_tpu.core.stage import load_stage
    model2 = load_stage(p)
    out2 = model2.transform(t)
    np.testing.assert_allclose(out1["scores"], out2["scores"],
                               rtol=1e-5, atol=1e-5)


def test_missing_output_raises():
    module, params, model = _make_model()
    model.set("fetchDict", {"y": "nonexistent"})
    feats = np.zeros((2, 4), dtype=np.float32)
    with pytest.raises(KeyError):
        model.transform(DataTable({"features": feats}))


def test_image_to_model_e2e():
    """images -> resize -> unroll -> TPUModel: the notebook-301 shape."""
    from mmlspark_tpu.core.schema import ImageSchema
    from mmlspark_tpu.stages.image import ImageTransformer, UnrollImage
    from mmlspark_tpu.core.stage import Pipeline

    rng = np.random.default_rng(5)
    rows = [ImageSchema.make_row(
        f"i_{i}.png", rng.integers(0, 256, (12, 12, 3), dtype=np.uint8))
        for i in range(6)]
    t = DataTable({"image": rows})

    in_dim = 8 * 8 * 3
    module = TinyMLP()
    params = module.init(jax.random.PRNGKey(1), jnp.ones((1, in_dim)))
    model = TPUModel.from_flax(module, params, inputCol="unrolled",
                               outputCol="scores")
    pipe = Pipeline([
        ImageTransformer().resize(8, 8),
        UnrollImage(),
        model,
    ])
    out = pipe.fit(t).transform(t)
    assert out["scores"].shape == (6, 3)


def test_int_token_model_inputs_stay_integer():
    # integer-token models (BiLSTM/Transformer) must receive int32 ids,
    # not float-coerced values (regression: embed rejects float input)
    from mmlspark_tpu.models.networks import build_network

    spec = {"type": "bilstm", "vocab_size": 20, "embed_dim": 4,
            "hidden": 4, "num_tags": 3}
    module = build_network(spec)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 6), jnp.int32))
    model = TPUModel.from_flax(module, variables, inputCol="tokens",
                               outputCol="tags", batchSize=4)
    toks = np.random.default_rng(0).integers(0, 20, size=(10, 6))
    out = model.transform(DataTable({"tokens": toks.astype(np.int64)}))
    assert out["tags"].shape == (10, 6, 3)
    # bfloat16 compute must also leave token ids alone
    model.set("computeDtype", "bfloat16")
    out2 = model.transform(DataTable({"tokens": toks.astype(np.int64)}))
    assert out2["tags"].shape == (10, 6, 3)


class TestShapeBuckets:
    """The serving compile-cache contract: explicit warmup compiles one
    executable per bucket, and steady-state traffic at ANY mix of batch
    sizes triggers ZERO further compiles (the recompile guard of the
    serving hot path — one stray XLA compile costs seconds on an
    accelerator)."""

    def _one_device_model(self, batch_size=64, dim=12):
        module, params, _ = None, None, None
        m = TinyMLP()
        params = m.init(jax.random.PRNGKey(0), jnp.ones((1, dim)))
        model = TPUModel.from_flax(m, params, inputCol="features",
                                   outputCol="scores",
                                   batchSize=batch_size)
        # 1-device mesh = the single-chip serving topology (the CI
        # 8-device mesh pads every batch to a multiple of 8, which
        # would mask a lost bucket)
        model.set_mesh(mesh_lib.make_mesh(
            {"data": 1}, devices=[jax.devices()[0]]))
        return model, dim

    def test_bucket_sizes_cover_batch_size(self):
        model, _ = self._one_device_model(batch_size=64)
        assert model.bucket_sizes() == [8, 16, 32, 64]
        model.set("batchSize", 48)        # non-power-of-two cap kept
        assert model.bucket_sizes() == [8, 16, 32, 48]

    def test_warmup_compiles_each_bucket_once(self):
        model, dim = self._one_device_model()
        compiles = model.warmup(
            {"features": np.zeros((1, dim), np.float32)})
        assert compiles == len(model.bucket_sizes())
        # warm again: everything cached
        assert model.warmup(
            {"features": np.zeros((1, dim), np.float32)}) == 0

    def test_steady_state_zero_recompiles_across_mixed_batch_sizes(self):
        model, dim = self._one_device_model()
        model.warmup({"features": np.zeros((1, dim), np.float32)})
        before = model.jit_cache_misses
        rng = np.random.default_rng(0)
        for rows in [1, 3, 8, 9, 17, 33, 64, 5, 50, 64, 2, 40, 31, 12]:
            t = DataTable({"features": rng.normal(
                size=(rows, dim)).astype(np.float32)})
            out = model.transform(t)
            assert len(out) == rows
        assert model.jit_cache_misses == before, (
            f"steady-state serving recompiled "
            f"{model.jit_cache_misses - before} time(s) across mixed "
            f"batch sizes — the bucket layer lost its shape cache")

    def test_metrics_expose_pad_device_and_misses(self):
        model, dim = self._one_device_model()
        model.transform(DataTable({"features": np.zeros(
            (4, dim), np.float32)}))
        m = model.metrics()
        assert m["jit_cache_misses"] >= 1
        assert m["pad_ms"]["count"] >= 1
        assert m["device_ms"]["count"] >= 1
