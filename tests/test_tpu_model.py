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


# ---------------------------------------------------------------------------
# weights are placed in the dtype the model function reads them in
# ---------------------------------------------------------------------------

LM_SPEC = {"type": "transformer", "vocab_size": 96, "dim": 32, "depth": 2,
           "heads": 4, "max_len": 16, "num_classes": 5}
LM_ROWS = np.random.default_rng(5).integers(0, 96, size=(11, 16))


def _one_device(model):
    return model.set_mesh(mesh_lib.make_mesh(
        {"data": 1}, devices=[jax.devices()[0]]))


def _lm(dtype="bfloat16", batch_size=16):
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": dtype, **LM_SPEC})
    variables = jax.jit(module.init)(jax.random.PRNGKey(3),
                                     jnp.zeros((1, 16), jnp.int32))
    model = TPUModel.from_flax(module, variables, inputCol="features",
                               outputCol="scores", batchSize=batch_size)
    return _one_device(model), dict(variables)


def _lm_table(rows=LM_ROWS):
    return DataTable({"features": rows.astype(np.float32)})


def _dtypes_by_path(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): str(a.dtype) for p, a in flat}


def _mlp_float32():
    _, params, model = _make_model()
    return model, DataTable({"features": np.ones((5, 4), np.float32)})


def _moe_bfloat16():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from latent_moe_tiny import ROWS, build
    module, params = build("bfloat16")
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=4)
    return model, DataTable({"features": ROWS.astype(np.float32)})


def _mlp_int8():
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"type": "mlp", "features": [16],
                            "num_classes": 3})
    weights = module.init(jax.random.PRNGKey(0), np.zeros((1, 6), np.float32))
    calib = np.random.default_rng(0).normal(size=(32, 6)).astype(np.float32)
    model = TPUModel.from_flax(module, weights, inputCol="features",
                               outputCol="scores", batchSize=16)
    return model.quantize({"features": calib}), \
        DataTable({"features": calib[:5]})


def _reads(how):
    """A ``from_fn`` function over {'w': (4, 3) float32, 'b': (3,)
    float32} that reads ``w`` in the way named."""
    def fn(weights, inputs):
        x, w = inputs["input"], weights["w"]
        if how == "converted":
            y = x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
        elif how == "raw_and_converted":
            y = x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16) + (x @ w)
        elif how == "two_narrow_dtypes":
            y = x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16) \
                + (x.astype(jnp.float16) @ w.astype(jnp.float16))
        elif how == "widened":
            y = x @ w.astype(jnp.float64 if jax.config.jax_enable_x64
                             else jnp.float32)
        elif how == "into_a_sub_jaxpr":
            y = jax.jit(lambda a, k: a @ k.astype(jnp.bfloat16))(
                x.astype(jnp.bfloat16), w)
        elif how == "sliced_then_converted":
            y = x[:, :2].astype(jnp.bfloat16) @ w[:2].astype(jnp.bfloat16)
        elif how == "returned":
            return {"output": x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16),
                    "kernel": w}
        return y.astype(jnp.float32) + weights["b"]
    return fn


class TestWeightPlacement:
    """``TPUModel._weights_on_device``: a floating leaf the function only
    ever converts to one narrower floating dtype is placed converted;
    everything else is placed as held; the outputs are the same."""

    def test_bfloat16_transformer_places_what_it_only_converts(self):
        model, held = _lm()
        model.transform(_lm_table())
        placed = _dtypes_by_path(model._device_weights)
        as_held = [p for p, dt in placed.items() if dt == "float32"]
        # every product kernel and bias of the blocks and the embedding
        # table: the function only converts them
        for name in ("qkv", "proj", "mlp_up", "mlp_down"):
            for leaf in ("kernel", "bias"):
                for block in ("block_0", "block_1"):
                    assert placed[f"['params']['{block}']['{name}']"
                                  f"['{leaf}']"] == "bfloat16"
        assert placed["['params']['embed']['embedding']"] == "bfloat16"
        # the float32 head meets a dot_general, the positions are
        # sliced before they are cast, LayerNorm reads float32
        assert sorted(as_held) == sorted(
            ["['params']['head']['kernel']", "['params']['head']['bias']",
             "['params']['pos_embed']",
             "['params']['ln_f']['scale']", "['params']['ln_f']['bias']"]
            + [f"['params']['block_{i}']['{ln}']['{leaf}']"
               for i in (0, 1) for ln in ("ln1", "ln2")
               for leaf in ("scale", "bias")])
        # the Param is what the caller gave
        assert set(_dtypes_by_path(model.get("weights")).values()) \
            == {"float32"}
        assert all(a is b for a, b in zip(
            jax.tree_util.tree_leaves(model.get("weights")),
            jax.tree_util.tree_leaves(held)))

    def test_transform_equals_the_unplaced_function(self):
        model, held = _lm()
        out = model.transform(_lm_table())["scores"]
        # the rule switched off: the function itself on the held tree,
        # at the padded bucket transform ran
        padded = np.concatenate([LM_ROWS, np.repeat(LM_ROWS[-1:], 5, 0)])
        direct = jax.jit(model.get("modelFn"))(
            held, {"input": jnp.asarray(padded, jnp.int32)})
        assert np.array_equal(out, np.asarray(direct)[:len(LM_ROWS)])

    def test_traced_forward_after_placement_converts_no_weight(self):
        model, _ = _lm()
        model.transform(_lm_table())
        placed = model._device_weights
        n = len(jax.tree_util.tree_leaves(placed))
        jaxpr = jax.make_jaxpr(model.get("modelFn"))(
            placed, {"input": jnp.zeros((16, 16), jnp.int32)}).jaxpr
        weights_in = set(jaxpr.invars[:n])
        converts = [e for e in jaxpr.eqns
                    if e.primitive.name == "convert_element_type"
                    and e.invars[0] in weights_in]
        assert converts == []

    @pytest.mark.parametrize("make", [_mlp_float32, _moe_bfloat16,
                                      _mlp_int8])
    def test_nothing_to_convert_places_every_leaf_as_held(self, make):
        model, table = make()
        model.transform(table)
        assert model.metrics()["weights_cast_leaves"] == 0
        assert model.metrics()["weights_cast_bytes"] == 0
        assert _dtypes_by_path(model._device_weights) == _dtypes_by_path(
            jax.tree_util.tree_map(jnp.asarray, model.get("weights")))

    @pytest.mark.parametrize("how, placed_as", [
        ("converted", "bfloat16"), ("raw_and_converted", "float32"),
        ("two_narrow_dtypes", "float32"), ("widened", "float32"),
        ("into_a_sub_jaxpr", "float32"),
        ("sliced_then_converted", "float32"), ("returned", "float32")])
    def test_from_fn_leaf_is_placed_by_how_it_is_read(self, how, placed_as):
        rng = np.random.default_rng(1)
        weights = {"w": rng.normal(size=(4, 3)).astype(np.float32),
                   "b": np.ones(3, np.float32)}
        model = _one_device(TPUModel.from_fn(
            _reads(how), weights, inputCol="x", outputCol="y", batchSize=8))
        x = rng.normal(size=(5, 4)).astype(np.float32)
        out = model.transform(DataTable({"x": x}))["y"]
        assert str(model._device_weights["w"].dtype) == placed_as
        assert str(model._device_weights["b"].dtype) == "float32"
        assert model.metrics()["weights_cast_leaves"] == int(
            placed_as == "bfloat16")
        padded = np.concatenate([x, np.repeat(x[-1:], 3, 0)])
        direct = jax.jit(_reads(how))(weights, {"input": padded})
        direct = direct["output"] if isinstance(direct, dict) else direct
        assert np.array_equal(out, np.asarray(direct, np.float32)[:5])

    def test_setting_weights_again_decides_again(self):
        model, held = _lm()
        out = model.transform(_lm_table())["scores"]
        first = model.metrics()
        assert first["weights_cast_leaves"] == 17
        # the same numbers, held in bfloat16 where the function reads
        # bfloat16: nothing is left to convert
        model.set("weights", jax.tree_util.tree_map(
            lambda a: np.asarray(a), model._device_weights))
        assert model._device_weights is None
        assert model.metrics()["weights_cast_leaves"] == 0
        again = model.transform(_lm_table())["scores"]
        assert model.metrics()["weights_cast_leaves"] == 0
        assert np.array_equal(out, again)
        model.set("weights", held)
        model.transform(_lm_table())
        assert model.metrics()["weights_cast_leaves"] == 17
        assert model.metrics()["weights_cast_bytes"] \
            == first["weights_cast_bytes"]

    def test_setting_model_fn_again_decides_again(self):
        weights = {"w": np.ones((4, 3), np.float32),
                   "b": np.ones(3, np.float32)}
        model = _one_device(TPUModel.from_fn(
            _reads("converted"), weights, inputCol="x", outputCol="y",
            batchSize=8))
        table = DataTable({"x": np.ones((3, 4), np.float32)})
        model.transform(table)
        assert str(model._device_weights["w"].dtype) == "bfloat16"
        model.set("modelFn", _reads("raw_and_converted"))
        model.transform(table)
        assert str(model._device_weights["w"].dtype) == "float32"
        assert model.metrics()["weights_cast_leaves"] == 0

    def test_resident_bytes_are_the_placed_trees(self):
        model, held = _lm()
        before = model.resident_bytes()
        assert before == sum(a.nbytes for a in
                             jax.tree_util.tree_leaves(held))
        model.transform(_lm_table())
        placed = sum(a.nbytes for a in jax.tree_util.tree_leaves(
            model._device_weights))
        assert model.resident_bytes() == placed
        assert before - placed == model.metrics()["weights_cast_bytes"]

    def test_metrics_carry_the_two_counters(self):
        model, held = _lm()
        assert model.metrics()["weights_cast_leaves"] == 0
        assert model.metrics()["weights_cast_bytes"] == 0
        model.transform(_lm_table())
        m = model.metrics()
        flat = _dtypes_by_path(model._device_weights)
        cast = [p for p, dt in flat.items() if dt == "bfloat16"]
        assert m["weights_cast_leaves"] == len(cast) == 17
        sizes = {jax.tree_util.keystr(p): a.size for p, a in
                 jax.tree_util.tree_flatten_with_path(held)[0]}
        assert m["weights_cast_bytes"] == 2 * sum(sizes[p] for p in cast)
        # and /metrics renders them from the same hook
        from mmlspark_tpu.core.prometheus import (
            PromRenderer, pipeline_families)
        r = PromRenderer()
        pipeline_families(r, model)
        assert "serving_model_weights_cast_leaves 17" in r.render()
        assert f"serving_model_weights_cast_bytes " \
               f"{m['weights_cast_bytes']}" in r.render()

    def test_later_buckets_reuse_the_placement(self):
        model, _ = _lm(batch_size=32)
        model.transform(_lm_table(LM_ROWS[:3]))
        placed = model._device_weights
        before = model.jit_cache_misses
        out = model.transform(_lm_table(np.tile(LM_ROWS, (3, 1))[:30]))
        assert model.jit_cache_misses == before + 1     # the 32 bucket
        assert model._device_weights is placed
        assert len(out) == 30

    def test_a_bucket_costs_one_python_trace_of_the_model_fn(self):
        calls = []

        def fn(weights, inputs):
            calls.append(inputs["input"].shape)      # runs when traced
            return (inputs["input"].astype(jnp.bfloat16)
                    @ weights["w"].astype(jnp.bfloat16)).astype(jnp.float32)
        model = _one_device(TPUModel.from_fn(
            fn, {"w": np.ones((4, 3), np.float32)}, inputCol="x",
            outputCol="y", batchSize=16))
        small = DataTable({"x": np.ones((3, 4), np.float32)})
        model.transform(small)
        # read once for the placement; the jitted forward is built from
        # that reading, not from a second trace
        assert calls == [(8, 4)]
        assert str(model._device_weights["w"].dtype) == "bfloat16"
        model.transform(DataTable({"x": np.ones((12, 4), np.float32)}))
        assert calls == [(8, 4), (16, 4)]
        model.transform(small)
        assert calls == [(8, 4), (16, 4)] and model.jit_cache_misses == 2

    def test_a_bucket_that_reads_a_leaf_differently_is_an_error(self):
        def fn(weights, inputs):
            x, w = inputs["input"], weights["w"]
            if x.shape[0] > 8:      # a wider bucket reads it raw
                return x @ w
            return (x.astype(jnp.bfloat16)
                    @ w.astype(jnp.bfloat16)).astype(jnp.float32)
        model = _one_device(TPUModel.from_fn(
            fn, {"w": np.ones((4, 3), np.float32)}, inputCol="x",
            outputCol="y", batchSize=16))
        model.transform(DataTable({"x": np.ones((3, 4), np.float32)}))
        assert str(model._device_weights["w"].dtype) == "bfloat16"
        with pytest.raises(ValueError, match="reads its weights differently"):
            model.transform(DataTable({"x": np.ones((12, 4), np.float32)}))

    def test_placed_as_held_before_a_batch_then_converted_from_that(self):
        from mmlspark_tpu.serving.sharded import device_residency
        model, held = _lm()
        full = sum(a.nbytes for a in jax.tree_util.tree_leaves(held))
        # a byte count before the first batch ships the tree as held
        assert device_residency(model)["total_bytes"] == full
        assert set(_dtypes_by_path(model._device_weights).values()) \
            == {"float32"}
        out = model.transform(_lm_table())["scores"]
        assert device_residency(model)["total_bytes"] \
            == full - model.metrics()["weights_cast_bytes"]
        fresh, _ = _lm()
        assert np.array_equal(out, fresh.transform(_lm_table())["scores"])

    def test_a_cast_leaf_keeps_its_declared_sharding(self):
        from jax.sharding import PartitionSpec as P
        model, _ = _lm()
        ref = model.transform(_lm_table())["scores"]
        sharded, _ = _lm()
        mesh = mesh_lib.make_mesh({"model": 2},
                                  devices=jax.devices()[:2])
        sharded.set_sharding(
            mesh, weight_specs=lambda path, leaf: P(None, "model")
            if "mlp_up']['kernel" in path else P(), in_spec=P())
        out = sharded.transform(_lm_table())["scores"]
        leaf = sharded._device_weights["params"]["block_1"]["mlp_up"][
            "kernel"]
        assert str(leaf.dtype) == "bfloat16"
        assert leaf.sharding.spec == P(None, "model")
        assert leaf.addressable_shards[0].data.shape == (32, 64)
        assert sharded.metrics()["weights_cast_leaves"] == 17
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
