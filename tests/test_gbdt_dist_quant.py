"""Comm-efficient quantized-histogram distributed GBDT (PR 19).

Pins the three contracts the quantized engine ships with:

* **Quantization accuracy** — hist_bits=16 holdout AUC within 0.005 of
  the f32 engine on the HIGGS shape (28 dense features), and the f32
  default is untouched (hist_bits=32 is bit-identical to leaving the
  knob off).
* **Reduce-scatter split search** — ``hist_comm='reduce_scatter'``
  grows the SAME forest as the psum oracle, bitwise, for both f32 and
  quantized histograms (integer accumulation makes the quantized pin
  exact on any device count; the f32 pin holds because per-cell
  reduction order is the only difference and XLA's ring keeps f32
  addition commutative per element).
* **Wire accounting** — the ring comm model halves (better) modeled
  bytes at hist_bits=16, the counters flow through the Prometheus
  exposition with bounded labels, and the fusion-kernel checker audits
  the quantized histogram kernels under the no-silent-f64-upcast rule.
"""
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                   # noqa: E402

from mmlspark_tpu.core import metrics as MC               # noqa: E402
from mmlspark_tpu.core.table import DataTable             # noqa: E402
from mmlspark_tpu.gbdt.booster import (                   # noqa: E402
    comm_payload_model, resolve_hist_method, train,
)
from mmlspark_tpu.parallel import mesh as mesh_lib        # noqa: E402


def _auc(y, p):
    """Rank AUC by hand (no sklearn dependency on the hot path)."""
    order = np.argsort(p, kind="stable")
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    n_pos = int((y == 1).sum())
    n_neg = len(y) - n_pos
    return (ranks[y == 1].sum() - n_pos * (n_pos + 1) / 2) / (
        n_pos * n_neg)


def _higgs_shape(n=6000, seed=7):
    """HIGGS-shaped synthetic binary task: 28 dense f32 features,
    nonlinear boundary, label noise."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 28)).astype(np.float32)
    logit = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2]
             + 0.4 * np.sin(2 * X[:, 3]) - 0.3 * X[:, 4] ** 2 + 0.3)
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


_KW = {"objective": "binary", "num_iterations": 6, "num_leaves": 15,
       "max_bin": 63, "min_data_in_leaf": 5}

_FOREST_KEYS = ("feature", "bin_threshold", "left", "right",
                "value", "count")


def _assert_forests_identical(a, b):
    for k in _FOREST_KEYS:
        np.testing.assert_array_equal(a.trees[k], b.trees[k], err_msg=k)


@pytest.fixture(scope="module")
def higgs_split():
    X, y = _higgs_shape()
    cut = 4096
    return X[:cut], y[:cut], X[cut:], y[cut:]


@pytest.fixture(scope="module")
def dist_forests(higgs_split, cpu_mesh_devices):
    """One training sweep shared by every pin below: serial/sharded x
    f32/q16 x psum/reduce_scatter on the same HIGGS-shaped data."""
    Xtr, ytr, _, _ = higgs_split
    mesh = mesh_lib.make_mesh()
    dp = {**_KW, "parallelism": "data"}
    return {
        "serial_f32": train(_KW, Xtr, ytr),
        "serial_q16": train({**_KW, "hist_bits": 16}, Xtr, ytr),
        "psum_f32": train({**dp, "hist_comm": "psum"}, Xtr, ytr,
                          mesh=mesh),
        "rs_f32": train({**dp, "hist_comm": "reduce_scatter"}, Xtr, ytr,
                        mesh=mesh),
        "psum_q16": train({**dp, "hist_bits": 16, "hist_comm": "psum"},
                          Xtr, ytr, mesh=mesh),
        "rs_q16": train({**dp, "hist_bits": 16,
                         "hist_comm": "reduce_scatter"}, Xtr, ytr,
                        mesh=mesh),
    }


class TestQuantizedAccuracy:
    def test_q16_auc_within_0005_of_f32(self, higgs_split, dist_forests):
        _, _, Xte, yte = higgs_split
        auc32 = _auc(yte, dist_forests["serial_f32"].predict(Xte))
        auc16 = _auc(yte, dist_forests["serial_q16"].predict(Xte))
        assert auc32 > 0.80, "f32 baseline failed to learn"
        assert abs(auc32 - auc16) < 0.005, (auc32, auc16)

    def test_f32_default_bit_identical_to_explicit_32(self, higgs_split,
                                                      dist_forests):
        # the unquantized engine must be byte-for-byte untouched:
        # hist_bits=32 (explicit) == knob absent (default)
        Xtr, ytr, _, _ = higgs_split
        b32 = train({**_KW, "hist_bits": 32}, Xtr, ytr)
        _assert_forests_identical(dist_forests["serial_f32"], b32)

    def test_q8_learns(self, higgs_split):
        Xtr, ytr, Xte, yte = higgs_split
        b8 = train({**_KW, "hist_bits": 8}, Xtr, ytr)
        # 8-bit rounding noise costs real AUC at 6 trees — the pinned
        # 0.005 accuracy contract is 16-bit only; 8-bit just has to
        # keep learning the signal
        assert _auc(yte, b8.predict(Xte)) > 0.70

    def test_q16_sharded_matches_serial(self, dist_forests):
        # stochastic rounding is keyed on GLOBAL row ids
        # (row0 = axis_index * shard_rows), so the integer histograms —
        # hence split structure and counts — are shard-invariant
        # bitwise; leaf values go through the quantization scale
        # delta = sum(|g|)/Q whose f32 sum is reassociated by the psum,
        # so values match to a couple of ULPs, not bitwise
        ser, dp = dist_forests["serial_q16"], dist_forests["psum_q16"]
        for k in ("feature", "bin_threshold", "left", "right", "count"):
            np.testing.assert_array_equal(ser.trees[k], dp.trees[k],
                                          err_msg=k)
        np.testing.assert_allclose(ser.trees["value"], dp.trees["value"],
                                   rtol=1e-5, atol=1e-7)


class TestReduceScatter:
    def test_f32_rs_matches_psum_oracle(self, dist_forests):
        _assert_forests_identical(dist_forests["psum_f32"],
                                  dist_forests["rs_f32"])

    def test_q16_rs_matches_psum_oracle(self, dist_forests):
        _assert_forests_identical(dist_forests["psum_q16"],
                                  dist_forests["rs_q16"])

    def test_q16_rs_reproducible(self, higgs_split, dist_forests,
                                 cpu_mesh_devices):
        Xtr, ytr, _, _ = higgs_split
        again = train({**_KW, "parallelism": "data", "hist_bits": 16,
                       "hist_comm": "reduce_scatter"}, Xtr, ytr,
                      mesh=mesh_lib.make_mesh())
        _assert_forests_identical(dist_forests["rs_q16"], again)

    def test_auto_comm_resolution(self, dist_forests):
        # auto -> reduce_scatter ONLY for quantized data-parallel
        assert dist_forests["serial_q16"].params["hist_comm"] == "psum"
        assert dist_forests["psum_f32"].params["hist_comm"] == "psum"

    def test_voting_composes_with_quantized_wire(self, cpu_mesh_devices):
        # PV-tree voting with k >= F sees every feature's candidate
        # slice; the voted slices ride the same int16 wire, so the
        # voted forest's STRUCTURE matches data-parallel bitwise.
        # Leaf values keep voting's standing contract (equal up to f32
        # reassociation between the sliced and full gain programs)
        rng = np.random.default_rng(3)
        X = rng.normal(size=(4096, 10)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
        mesh = mesh_lib.make_mesh()
        kw = {"objective": "binary", "num_iterations": 4,
              "num_leaves": 4, "max_bin": 31, "hist_bits": 16}
        bd = train({**kw, "parallelism": "data", "hist_comm": "psum"},
                   X, y, mesh=mesh)
        bv = train({**kw, "parallelism": "voting", "top_k": 10},
                   X, y, mesh=mesh)
        for k in ("feature", "bin_threshold", "left", "right", "count"):
            np.testing.assert_array_equal(bd.trees[k], bv.trees[k],
                                          err_msg=k)
        np.testing.assert_allclose(bd.trees["value"], bv.trees["value"],
                                   rtol=1e-4, atol=1e-6)


class TestHistKnobValidation:
    def test_auto_routes_pallas_only_on_tpu(self):
        assert resolve_hist_method("auto", "tpu", 255) == "pallas"
        assert resolve_hist_method("auto", "cpu", 255) == "scatter"
        assert resolve_hist_method("auto", "gpu", 255) == "scatter"
        # explicit requests are honored (pallas runs interpret off-TPU)
        assert resolve_hist_method("scatter", "tpu", 255) == "scatter"
        assert resolve_hist_method("pallas", "cpu", 255) == "pallas"

    def test_pallas_beyond_vmem_tiling_degrades_to_onehot(self):
        assert resolve_hist_method("pallas", "tpu", 4095) == "onehot"

    def test_unsupported_hist_bits_fails_actionably(self):
        X = np.zeros((64, 2), np.float32)
        y = np.zeros(64, np.float32)
        with pytest.raises(ValueError, match="hist_bits=12"):
            train({"objective": "regression", "hist_bits": 12}, X, y)

    @pytest.mark.parametrize("method,match", [
        ("onehot", "onehot"),
        # Mosaic refuses an int16 matmul on TPU, so the knob refuses
        # the pair on every backend rather than interpreting off-chip
        ("pallas", "hist_bits=8 or 32"),
    ])
    def test_quantized_unsupported_method_fails_actionably(self, method,
                                                           match):
        X = np.zeros((64, 2), np.float32)
        y = np.zeros(64, np.float32)
        with pytest.raises(ValueError, match=match):
            train({"objective": "regression", "hist_bits": 16,
                   "hist_method": method}, X, y)

    def test_quantized_feature_parallel_fails(self, cpu_mesh_devices):
        X = np.zeros((64, 2), np.float32)
        y = np.zeros(64, np.float32)
        with pytest.raises(ValueError, match="feature"):
            train({"objective": "regression", "hist_bits": 16,
                   "parallelism": "feature"}, X, y,
                  mesh=mesh_lib.make_mesh())

    def test_reduce_scatter_needs_data_parallel(self, cpu_mesh_devices):
        X = np.zeros((64, 2), np.float32)
        y = np.zeros(64, np.float32)
        with pytest.raises(ValueError, match="reduce_scatter"):
            train({"objective": "regression",
                   "hist_comm": "reduce_scatter",
                   "parallelism": "voting"}, X, y,
                  mesh=mesh_lib.make_mesh())

    def test_estimator_plumbs_hist_knobs(self):
        from mmlspark_tpu.gbdt.estimators import TPUBoostClassifier
        rng = np.random.default_rng(0)
        X = rng.normal(size=(256, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        t = DataTable({"features": X, "label": y})
        clf = TPUBoostClassifier(numIterations=3, histBits=16,
                                 histComm="auto")
        model = clf.fit(t)
        assert model._booster.params["hist_bits"] == 16
        # serial fit: auto must stay psum
        assert model._booster.params["hist_comm"] == "psum"


class TestCommModel:
    def test_quantized_wire_halves_psum_bytes(self):
        a32 = comm_payload_model("data", "psum", 32, 10, 31, 28, 255,
                                 4, 20, 10000)
        a16 = comm_payload_model("data", "psum", 16, 10, 31, 28, 255,
                                 4, 20, 10000)
        assert a32["psum"] == pytest.approx(2 * (
            a16["psum"] - 10 * 2 * 12 * 3 / 4))   # minus scale psums

    def test_reduce_scatter_divides_wire_by_device_count(self):
        # the histogram tensor crosses the wire once (S*(D-1)/D) vs the
        # allreduce's 2*S*(D-1)/D, and only owned features ship onward
        # f32 pair: no per-tree scale psums, so the identity is exact
        # (F=32 divides D=4 -> no feature padding)
        ps = comm_payload_model("data", "psum", 32, 10, 31, 32, 255,
                                4, 20, 10000)
        rs = comm_payload_model("data", "reduce_scatter", 32, 10, 31,
                                32, 255, 4, 20, 10000)
        assert rs["psum_scatter"] == pytest.approx(ps["psum"] / 2)
        ps16 = comm_payload_model("data", "psum", 16, 10, 31, 32, 255,
                                  4, 20, 10000)
        rs16 = comm_payload_model("data", "reduce_scatter", 16, 10, 31,
                                  32, 255, 4, 20, 10000)
        # slightly under 2x at the same bit width: the (3, B) leaf-total
        # psum rides along so the split table keeps psum's association
        assert sum(rs16.values()) < sum(ps16.values()) / 1.8

    def test_q16_total_at_least_2x_under_f32(self):
        f32 = sum(comm_payload_model("data", "psum", 32, 10, 31, 28,
                                     255, 4, 20, 10000).values())
        q16 = sum(comm_payload_model("data", "reduce_scatter", 16, 10,
                                     31, 28, 255, 4, 20, 10000).values())
        assert f32 / q16 >= 2.0

    def test_single_device_models_zero(self):
        z = comm_payload_model("data", "psum", 16, 10, 31, 28, 255,
                               1, 20, 10000)
        assert sum(z.values()) == 0

    def test_unknown_collective_rejected(self):
        with pytest.raises(ValueError, match="all_reduce"):
            MC.gbdt_comm_add("all_reduce", 1.0)

    def test_train_records_comm_bytes(self, dist_forests):
        info = dist_forests["rs_q16"].train_info
        assert info["comm_bytes"]["psum_scatter"] > 0
        assert info["comm_bytes"]["all_gather"] > 0
        assert "comm_bytes" not in dist_forests["serial_f32"].train_info

    def test_exposition_carries_new_families(self, dist_forests):
        from mmlspark_tpu.core.prometheus import (PromRenderer,
                                                  process_families)
        assert sum(MC.gbdt_comm_counters().values()) > 0, \
            "dist_forests fixture should have recorded comm bytes"
        MC.gbdt_hist_histograms()["build"].observe(1.25)
        r = PromRenderer()
        process_families(r)
        text = r.render()
        assert 'gbdt_comm_bytes_total{collective="psum_scatter"}' in text
        assert 'gbdt_hist_phase_ms_bucket{phase="build"' in text
        assert "# HELP gbdt_comm_bytes_total" in text


def _bad_quant_kernel(hist):
    # deliberately violates the no-silent-f64-upcast rule
    return hist.astype(jnp.float64).cumsum(axis=-1)


class TestQuantHistCheckerRules:
    @pytest.fixture(autouse=True)
    def _tools_path(self):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        yield
        sys.path.pop(0)

    def test_quanthist_names_get_f64_rule(self):
        import check_fusion_kernels as chk
        assert chk.is_quantized_kernel("gbdt.quanthist.build_histogram")
        assert chk.is_quantized_kernel("gbdt.quanthist.hist_kernel")
        assert not chk.is_quantized_kernel("gbdt.tree.predict_trees")

    def test_quanthist_kernels_registered_and_clean(self):
        import check_fusion_kernels as chk
        from mmlspark_tpu.core.fusion import KERNEL_REGISTRY
        chk.register_known_callees()
        names = set(KERNEL_REGISTRY.values())
        for want in ("gbdt.quanthist.build_histogram",
                     "gbdt.quanthist.hist_scatter",
                     "gbdt.quanthist.stats_block",
                     "gbdt.quanthist.hist_kernel",
                     "gbdt.quanthist.hist_kernel_nibble"):
            assert want in names, f"{want} not in kernel audit"
        import inspect
        import textwrap
        for code, name in list(KERNEL_REGISTRY.items()):
            if not name.startswith("gbdt.quanthist."):
                continue
            lines, first = inspect.getsourcelines(code)
            src = textwrap.dedent("".join(lines))
            assert chk._check_source(name, src, first, lines) == []

    def test_checker_catches_f64_upcast_in_quant_kernel(self):
        import inspect
        import textwrap
        import check_fusion_kernels as chk
        lines, first = inspect.getsourcelines(_bad_quant_kernel)
        src = textwrap.dedent("".join(lines))
        bad = chk._check_source("gbdt.quanthist.bad", src, first, lines)
        assert any("float64" in v for v in bad), bad
        # same source under a NON-quantized name passes the f64 rule
        ok = chk._check_source("gbdt.other.bad", src, first, lines)
        assert not any("float64" in v for v in ok)
