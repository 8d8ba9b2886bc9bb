"""Booster: GBDT training driver + serialized model.

Capability parity with the reference's `LightGBMBooster`
(ref: src/lightgbm/src/main/scala/LightGBMBooster.scala:14-60 — model
string serialization, lazy scoring, saveNativeModel, feature importances)
and its train loop (ref: TrainUtils.scala:71-107 — booster create, iterate
``LGBM_BoosterUpdateOneIter``, early stopping via modelString warm start).

TPU design: the dataset is binned once on host, shipped to HBM once, and
every boosting iteration is a jitted program (gradients → tree growth →
score update). Data-parallel mode wraps the iteration in ``shard_map``
over the mesh's data axis with psum'd histograms — the ICI equivalent of
``LGBM_NetworkInit``'s socket allreduce ring (ref: TrainUtils.scala:207).
"""

from __future__ import annotations

import collections
import functools
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.gbdt import binning as binning_lib
from mmlspark_tpu.gbdt.binning import BinMapper
from mmlspark_tpu.gbdt.objectives import Objective, get_objective
from mmlspark_tpu.gbdt.tree import (
    GrowParams, Tree, grow_tree, predict_trees, sample_iteration_masks,
)
from mmlspark_tpu.parallel import mesh as mesh_lib

# trace-time counters: each entry increments when XLA (re)traces the
# named program, so `trace_counts()` deltas across repeated train()
# calls at the same shapes are the chunk-fn-cache regression guard
# (tests/test_perf_floors.py) — steady state must add ZERO traces.
TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_counts() -> Dict[str, int]:
    """Snapshot of boosting-program trace counters (recompile guard)."""
    return dict(TRACE_COUNTS)

DEFAULTS: Dict[str, Any] = {
    # names mirror the reference's TrainParams (TrainParams.scala:9-61)
    "objective": "regression",
    "num_iterations": 100,
    "learning_rate": 0.1,
    "num_leaves": 31,
    "max_bin": 255,
    "max_depth": 0,
    "min_data_in_leaf": 20,
    "min_sum_hessian_in_leaf": 1e-3,
    "lambda_l1": 0.0,
    "lambda_l2": 0.0,
    "min_gain_to_split": 0.0,
    "feature_fraction": 1.0,
    "bagging_fraction": 1.0,
    "bagging_freq": 0,
    "num_class": 1,
    "boost_from_average": True,
    "early_stopping_round": 0,
    "seed": 0,
    "alpha": 0.9,                      # quantile / huber
    "tweedie_variance_power": 1.5,
    "hist_method": "auto",  # 'auto' | 'scatter' | 'onehot' | 'pallas'
    # histogram precision (Shi et al., NeurIPS'22 quantized GBDT):
    # 32 = classic f32 (bit-identical to the pre-quantization engine);
    # 16/8 = per-round gradients stochastically rounded to narrow ints,
    # exact int32 histogram accumulation, int16 collective wire (2x
    # fewer bytes than f32), one dequantize at split-gain time
    "hist_bits": 32,
    # data-parallel histogram collective: 'psum' allreduces the full
    # (3, F, B) tensor to every device; 'reduce_scatter' partitions
    # features across devices (O(F*B/D) wire; LightGBM's distributed
    # recipe) and exchanges only (D, 4) split candidates. 'auto' keeps
    # psum for f32 (bit-compat) and picks reduce_scatter for quantized
    # data-parallel runs, where the wire saving is the point.
    "hist_comm": "auto",
    "parallelism": "serial",  # 'serial' | 'data' | 'feature' | 'voting'
    "top_k": 20,               # voting-parallel candidates per worker
    # iterations fused per host dispatch (lax.scan chunk); 0 = auto
    # (8 for runs long enough to amortize the chunk compile, else 1);
    # with early stopping every chunk is capped at esr_sync so the
    # async loss-read contract holds
    "boost_chunk": 0,
    # 'auto' bins on device when the mapper's cuts are f32-exact
    # (float32 input) and the input is dense single-host; 'off' forces
    # host binning; 'on' asks for device binning and warns (falling
    # back) when ineligible
    "device_binning": "auto",
    # how streaming/multi-host ingest fits bin boundaries: 'sample' =
    # the reservoir-sample-then-fit discipline (LightGBM
    # bin_construct_sample_cnt analog; boundaries from <=200k rows);
    # 'sketch' = BinMapper.fit_streaming — a mergeable quantile sketch
    # sees EVERY row in one bounded-memory pass (Chen & Guestrin §3.3 /
    # GK), and multi-host fits agree by exchanging per-host sketches
    # instead of gathering sample rows. Dense in-memory input ignores
    # this (one-shot fit sees everything already).
    "bin_fit": "sample",
    # keep the device-resident training state (binned matrix, running
    # scores, forest buffer) on the returned Booster so
    # boost_more(data=None) continues boosting EXACTLY where train()
    # stopped — bit-identical to having trained longer in one call.
    # Costs the binned matrix's HBM for the Booster's lifetime;
    # single-host, early-stopping-off runs only.
    "keep_training_data": False,
}


class Booster:
    """A trained forest, serializable to a model string."""

    def __init__(self, objective: Objective, trees: Dict[str, np.ndarray],
                 init_score: np.ndarray, num_class: int,
                 feature_names: List[str], params: Dict[str, Any],
                 best_iteration: int = -1, tree_depths: Optional[List[int]] = None):
        self.objective = objective
        self.trees = trees  # stacked arrays (T, M): feature/threshold/left/right/value/is_leaf/gain/count
        self.init_score = np.asarray(init_score, dtype=np.float64)
        self.num_class = int(num_class)
        self.feature_names = list(feature_names)
        self.params = dict(params)
        self.best_iteration = int(best_iteration)
        self.tree_depths = list(tree_depths or [])
        self._f64_flag: Optional[bool] = None   # _needs_f64_inference cache
        # device-resident tree arrays, keyed by the t_limit they were
        # built for (raw_score used to re-upload the whole forest on
        # every call); invalidated whenever t_limit changes
        self._dev_forest: Optional[Tuple[int, Dict[str, Any]]] = None
        # per-phase fit wall seconds (set by train(); empty for loaded
        # models): {bin, ship[, bin_device], first_iter, boost, fetch}
        self.train_timing: Dict[str, float] = {}
        # non-numeric fit facts (set by train()): bin_path
        # ('device'|'host'), boost_chunk (fused iterations per
        # dispatch), boost_chunks (dispatch count)
        self.train_info: Dict[str, Any] = {}
        # incremental-refresh state (set by train(); both in-memory
        # only — a Booster rebuilt from a model string has neither):
        # the frozen BinMapper for boost_more on fresh data, and the
        # retained device training state for exact continuation
        self.bin_mapper = None
        self._resume: Optional[Dict[str, Any]] = None

    # -- inference ----------------------------------------------------------

    @property
    def num_trees(self) -> int:
        return 0 if not self.trees else int(self.trees["feature"].shape[0])

    def _max_depth(self, t_limit: int) -> int:
        depths = self.tree_depths[:t_limit] or [
            self.params.get("num_leaves", 31) - 1]
        return max(1, max(depths))

    def _needs_f64_inference(self) -> bool:
        """True when the jitted f32 walk could misroute rows. Primary
        signal: the fit-time flag recorded from the BinMapper's true
        data gaps ('f32_unsafe' in params). Fallback for models saved
        without the flag: thresholds beyond f32's 24-bit integer range
        (timestamps/IDs), or PER-FEATURE threshold spacing below the
        f32 rounding band. Such forests score on host in float64.
        Cached — trees are immutable after construction."""
        if self._f64_flag is None:
            self._f64_flag = self._compute_f64_flag()
        return self._f64_flag

    def _compute_f64_flag(self) -> bool:
        if "f32_unsafe" in self.params:
            return bool(self.params["f32_unsafe"])
        if not self.trees:
            return False
        internal = ~self.trees["is_leaf"].astype(bool)
        thr = self.trees["threshold"][internal]
        feats = self.trees["feature"][internal]
        keep = np.isfinite(thr)
        thr, feats = thr[keep], feats[keep]
        if not len(thr):
            return False
        if np.abs(thr).max() >= 2.0 ** 24:
            return True
        eps32 = float(np.finfo(np.float32).eps)
        for fid in np.unique(feats):
            t = np.unique(thr[feats == fid])
            if len(t) < 2:
                continue
            gaps = np.diff(t)
            band = 8.0 * eps32 * np.maximum(np.abs(t[:-1]), np.abs(t[1:]))
            if (gaps <= band).any():
                return True
        return False

    def raw_score(self, X: np.ndarray,
                  num_iteration: Optional[int] = None) -> np.ndarray:
        """Raw margin scores, shape (N,) or (K, N) for multiclass.
        CSRMatrix inputs score through chunked densification (8192 rows
        at a time) — bounded memory at any feature width."""
        from mmlspark_tpu.core.sparse import CSRMatrix
        if isinstance(X, CSRMatrix):
            if X.shape[0] == 0:
                return self.raw_score(
                    np.zeros((0, len(self.feature_names))), num_iteration)
            # rows per chunk from a ~256 MB dense budget, so memory stays
            # bounded at ANY feature width
            step = max(1, min(8192, (256 << 20) // (4 * X.shape[1])))
            outs = [self.raw_score(X[lo:min(lo + step, X.shape[0])]
                                   .toarray(), num_iteration)
                    for lo in range(0, X.shape[0], step)]
            return np.concatenate(outs, axis=-1)
        n = np.asarray(X).shape[0]
        K = self.num_class
        it = self._resolve_iterations(num_iteration)
        t_limit = it * K
        scores = np.broadcast_to(
            self.init_score[:, None].astype(np.float32), (K, n)).copy()
        if t_limit > 0 and self.num_trees > 0:
            if self._needs_f64_inference():
                out = _host_predict_trees(
                    np.asarray(X, dtype=np.float64),
                    {k: v[:t_limit] for k, v in self.trees.items()},
                    self._max_depth(t_limit))
            else:
                dev = self._device_trees(t_limit)
                out = np.asarray(predict_trees(
                    jnp.asarray(np.asarray(X, dtype=np.float32)),
                    dev["feature"], dev["threshold"], dev["left"],
                    dev["right"], dev["value"],
                    max_depth=self._max_depth(t_limit)))   # (T, N)
            out = out.reshape(it, K, n).sum(axis=0)
            scores += out
        return scores[0] if K == 1 else scores

    def _device_trees(self, t_limit: int) -> Dict[str, Any]:
        """Device-resident stacked tree arrays for the jitted f32 walk.
        Cached on the Booster (building five jnp arrays per predict()
        call re-shipped the whole forest every time — it dominated
        small-batch scoring); invalidated when ``t_limit`` changes
        (num_iteration / best_iteration truncation picks new rows)."""
        cached = self._dev_forest
        if cached is None or cached[0] != t_limit:
            arrs = {k: jnp.asarray(self.trees[k][:t_limit])
                    for k in ("feature", "threshold", "left", "right",
                              "value")}
            cached = (int(t_limit), arrs)
            self._dev_forest = cached
        # return the LOCAL tuple, not a re-read of the attribute: a
        # concurrent predict() with a different t_limit may swap the
        # cache between the check above and this return
        return cached[1]

    def predict(self, X: np.ndarray,
                num_iteration: Optional[int] = None) -> np.ndarray:
        """Transformed prediction (probability / mean). Multiclass returns
        (N, K) probabilities."""
        raw = self.raw_score(X, num_iteration)
        out = np.asarray(self.objective.transform(jnp.asarray(raw)))
        return out.T if self.num_class > 1 else out

    def _resolve_iterations(self, num_iteration: Optional[int]) -> int:
        total = self.num_trees // max(self.num_class, 1)
        if num_iteration is not None and num_iteration > 0:
            return min(num_iteration, total)
        if self.best_iteration > 0:
            return min(self.best_iteration, total)
        return total

    # -- introspection ------------------------------------------------------

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Per-feature split counts or total gain
        (ref: LightGBMBooster.getFeatureImportances)."""
        f = len(self.feature_names)
        out = np.zeros(f)
        if self.num_trees == 0:
            return out
        internal = ~self.trees["is_leaf"].astype(bool)
        feats = self.trees["feature"][internal]
        if importance_type == "split":
            np.add.at(out, feats, 1.0)
        elif importance_type == "gain":
            np.add.at(out, feats, self.trees["gain"][internal])
        else:
            raise ValueError(f"importance_type {importance_type!r}")
        return out

    # -- incremental refresh (continued boosting) ---------------------------

    def boost_more(self, num_iterations: int, X=None,
                   y: Optional[np.ndarray] = None,
                   sample_weight: Optional[np.ndarray] = None,
                   valid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                   mesh: Optional[Mesh] = None) -> "Booster":
        """Append ``num_iterations`` boosting rounds and return the
        grown forest as a NEW Booster (this one is untouched apart from
        its retained device state being consumed — see below). The
        online-refresh path of the model-lifecycle story: keep serving
        the old forest while the new one trains, then hot-swap.

        Two modes:

        - ``X is None`` — EXACT continuation on the retained training
          state (requires ``train(..., {'keep_training_data': True})``).
          The device-resident binned matrix, running scores, and forest
          buffer pick up exactly where train() stopped, so the result
          is bit-identical to having trained ``it + num_iterations``
          rounds in one call (chunk-length invariance is pinned by the
          PR 3 parity suite; continuation just adds chunks). The
          retained state is single-use: the jitted chunk donates its
          score/forest buffers, so after this call the state moves to
          the RETURNED booster and this one's is marked consumed.

        - ``X, y`` given — continued boosting on FRESH data against the
          FROZEN ``bin_mapper``: new data bins with the original cuts
          (identical split semantics to the base forest; drifted values
          clamp into the original bin range), the base forest scores
          the new rows once, and new trees append. Deterministic for
          fixed inputs; per-iteration sampling masks continue at the
          base forest's iteration index, so a bagged continuation
          doesn't replay the base run's bags."""
        if num_iterations <= 0:
            raise ValueError(
                f"num_iterations must be positive: {num_iterations}")
        if X is None:
            if y is not None or sample_weight is not None \
                    or valid is not None:
                raise ValueError(
                    "boost_more(data=None) continues on the retained "
                    "training state; y/sample_weight/valid only apply "
                    "with fresh X")
            return self._boost_more_retained(int(num_iterations))
        if self.bin_mapper is None:
            raise ValueError(
                "this Booster carries no BinMapper (rebuilt from a "
                "model string?); boost_more on fresh data needs the "
                "frozen fit-time binning — keep the trained Booster "
                "object, or refit")
        params = {k: v for k, v in self.params.items() if k in DEFAULTS}
        params["num_iterations"] = int(num_iterations)
        # the fresh-data path rides the init_model warm start, which
        # cannot retain continuation state by design — carrying the
        # flag through would only trigger train()'s ineligibility
        # warning on every refresh cycle
        params.pop("keep_training_data", None)
        if valid is None:
            params["early_stopping_round"] = 0
        return train(params, X, y, sample_weight=sample_weight,
                     valid=valid, feature_names=self.feature_names,
                     mesh=mesh, init_model=self,
                     bin_mapper=self.bin_mapper)

    def _boost_more_retained(self, extra: int) -> "Booster":
        st = self._resume
        if st is None:
            raise ValueError(
                "no retained training state: pass "
                "{'keep_training_data': True} to train() (single-host, "
                "no init_model, no early stopping) to enable "
                "boost_more(data=None)")
        if st["consumed"]:
            raise ValueError(
                "retained training state already consumed: the jitted "
                "chunk donates its buffers, so continuation chains "
                "through the NEWEST booster returned by boost_more")
        import time as _time
        t_start = _time.perf_counter()
        K, it0 = st["K"], st["it_done"]
        total = it0 + extra
        forest, t_cap = st["forest"], st["t_cap"]
        need = total * K
        new_cap = t_cap
        while new_cap < need:
            new_cap *= 2    # keep the pow-2 capacity-bucket discipline
        if new_cap != t_cap:
            grow = new_cap - t_cap
            # grown rows are written before they are ever read, so the
            # pad values are inert (left/right 0 self-reference included)
            forest = Tree(*[jnp.pad(getattr(forest, fld),
                                    ((0, grow), (0, 0)))
                            for fld in Tree._fields])
        scores = st["scores"]
        S_cfg = int(self.params.get("boost_chunk", 0) or 0)
        if S_cfg <= 0:
            S_cfg = 8 if extra >= 16 else 1
        S_cfg = max(1, min(S_cfg, extra))
        # consumed BEFORE the first dispatch: the chunk donates the
        # score/forest buffers, so a mid-loop failure (compile error,
        # OOM on a grown buffer, interrupt) must not leave a state that
        # passes the guard while pointing at deleted device arrays
        st["consumed"] = True
        it = it0
        n_chunks = 0
        while it < total:
            S = min(S_cfg, total - it)
            chunk_fn = _make_chunk_step(
                st["obj_key"], st["gp"], st["lr"], K, st["axis_name"],
                st["mesh"], st["parallel_mode"], S, st["bag_cfg"],
                st["ff_cfg"], st["f"], st["f_eff"])
            scores, forest = chunk_fn(
                st["bins_d"], scores, st["y_d"], st["w_d"],
                st["fmask_base"], forest, np.int32(it), st["mask_key"])
            n_chunks += 1
            it += S
        jax.block_until_ready(scores)
        trees_done = total * K
        host = jax.device_get(forest._asdict())
        stacked = {name: arr[:trees_done] for name, arr in host.items()}
        mapper = st["mapper"]
        thr_lut = mapper.threshold_matrix(st["num_bins"])
        thr = thr_lut[stacked["feature"], stacked["bin_threshold"]]
        stacked["threshold"] = np.where(stacked["is_leaf"], 0.0, thr)
        stacked["value"] = stacked["value"] * st["lr"]
        tree_depths = [
            _tree_depth({k: v[t] for k, v in stacked.items()})
            for t in range(trees_done)]
        p2 = dict(self.params)
        p2["num_iterations"] = total
        booster = Booster(self.objective, stacked, st["init_score"], K,
                          st["feature_names"], p2, best_iteration=-1,
                          tree_depths=tree_depths)
        booster.bin_mapper = mapper
        booster._resume = {**st, "scores": scores, "forest": forest,
                           "it_done": total, "t_cap": new_cap,
                           "consumed": False}
        booster.train_timing = {
            "boost": round(_time.perf_counter() - t_start, 3)}
        booster.train_info = {"bin_path": "retained",
                              "boost_chunk": S_cfg,
                              "boost_chunks": n_chunks}
        return booster

    # -- serialization ------------------------------------------------------

    def model_to_string(self) -> str:
        d = {
            "format": "mmlspark_tpu.booster.v1",
            "objective": self.objective.name,
            "objective_config": {
                "num_class": self.num_class,
                "alpha": getattr(self.objective, "alpha", None),
                "rho": getattr(self.objective, "rho", None),
            },
            "num_class": self.num_class,
            "init_score": self.init_score.tolist(),
            "feature_names": self.feature_names,
            "best_iteration": self.best_iteration,
            "tree_depths": self.tree_depths,
            "params": {k: v for k, v in self.params.items()
                       if isinstance(v, (int, float, str, bool))},
            "trees": {k: v.tolist() for k, v in self.trees.items()},
        }
        return json.dumps(d)

    @staticmethod
    def from_string(s: str) -> "Booster":
        d = json.loads(s)
        cfg = d.get("objective_config", {})
        alpha = cfg.get("alpha")
        rho = cfg.get("rho")
        obj = get_objective(
            d["objective"], num_class=d["num_class"],
            alpha=0.9 if alpha is None else alpha,
            tweedie_variance_power=1.5 if rho is None else rho)
        tree_dtypes = {"feature": np.int32, "threshold": np.float64,
                       "left": np.int32, "right": np.int32,
                       "value": np.float32, "is_leaf": bool,
                       "gain": np.float32, "count": np.float32,
                       "bin_threshold": np.int32}
        trees = {k: np.asarray(v, dtype=tree_dtypes.get(k, np.float32))
                 for k, v in d["trees"].items()}
        return Booster(obj, trees, np.asarray(d["init_score"]),
                       d["num_class"], d["feature_names"], d["params"],
                       d.get("best_iteration", -1), d.get("tree_depths"))

    def save_native_model(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.model_to_string())

    @staticmethod
    def load_native_model(path: str) -> "Booster":
        with open(path) as f:
            return Booster.from_string(f.read())


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


_RESERVOIR_CAP = 200_000


def _reservoir_rows(shard_iter, cap: int, seed: int) -> np.ndarray:
    """Uniform row sample across an entire shard stream (bounded memory,
    one pass) — vectorized Algorithm R over row blocks. This is the
    LightGBM BinMapper discipline: sample the WHOLE dataset, not the
    head (ref: LGBM bin_construct_sample_cnt over the full data)."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    buf: Optional[np.ndarray] = None
    seen = 0
    for shard in shard_iter:
        Xs = np.asarray(shard[0], dtype=np.float64)
        i = 0
        if buf is None:
            take = min(cap, len(Xs))
            buf = Xs[:take].copy()
            seen = take
            i = take
        elif len(buf) < cap:
            take = min(cap - len(buf), len(Xs))
            buf = np.concatenate([buf, Xs[:take]])
            seen += take
            i = take
        rest = Xs[i:]
        if len(rest):
            t = seen + np.arange(1, len(rest) + 1)
            accept = rng.random(len(rest)) < (cap / t)
            n_acc = int(accept.sum())
            if n_acc:
                buf[rng.integers(0, cap, size=n_acc)] = rest[accept]
            seen += len(rest)
    if buf is None:
        raise ValueError("empty shard stream")
    return buf


def _multihost_sketch_mapper(X, streaming: bool, max_bin: int,
                             nproc: int) -> BinMapper:
    """Distributed bin-boundary agreement WITHOUT gathering rows: each
    host folds its LOCAL data into per-feature mergeable quantile
    sketches (gbdt/sketch.py), the fixed-shape sketch summaries are
    allgathered bit-exactly (f64 as uint32 pairs, like the row wire
    below), and every host merges the SAME per-host summaries in
    process order — so all hosts derive identical cuts from statistics
    of EVERY row, at O(F · width) wire bytes instead of O(sample · F)
    rows (the Chen & Guestrin §3.3 distributed-sketch recipe)."""
    from jax.experimental import multihost_utils
    from mmlspark_tpu.gbdt.sketch import QuantileSketch
    from mmlspark_tpu.core.sparse import CSRMatrix
    wire_width = 512
    sketches: List[QuantileSketch] = []

    def absorb(block: np.ndarray) -> None:
        block = np.asarray(block)
        if not sketches:
            sketches.extend(QuantileSketch()
                            for _ in range(block.shape[1]))
        for j, sk in enumerate(sketches):
            sk.update(block[:, j])

    if streaming:
        if not (isinstance(X, (list, tuple)) or callable(X)):
            raise ValueError(
                "multi-host streaming GBDT needs a replayable shard "
                "sequence (list or zero-arg factory), not a one-shot "
                "generator: bin boundaries must be agreed across hosts "
                "before any shard is binned")
        fac = X if callable(X) else (lambda: iter(X))
        for shard in fac():
            absorb(shard[0])
    elif isinstance(X, CSRMatrix):
        # bounded densification (the CSR fit path keeps no dense copy)
        step = max(1, (64 << 20) // max(1, X.shape[1] * 8))
        for i in range(0, X.shape[0], step):
            absorb(X.take(np.arange(i, min(i + step, X.shape[0])))
                   .toarray())
        if not sketches:
            absorb(np.empty((0, X.shape[1])))
    else:
        absorb(np.asarray(X))
    wire = np.stack([sk.to_wire(wire_width) for sk in sketches])
    as_u32 = np.ascontiguousarray(wire, dtype=np.float64).view(np.uint32)
    gathered = np.ascontiguousarray(np.asarray(
        multihost_utils.process_allgather(as_u32)))
    gathered = gathered.reshape(nproc, *as_u32.shape).view(np.float64)
    merged = [QuantileSketch.from_wire(gathered[0, j])
              for j in range(len(sketches))]
    for h in range(1, nproc):
        for j, sk in enumerate(merged):
            sk.merge(QuantileSketch.from_wire(gathered[h, j]))
    return BinMapper.fit_streaming([], max_bin=max_bin, sketches=merged)


def _multihost_mapper(X, streaming: bool, max_bin: int, seed: int,
                      nproc: int, bin_fit: str = "sample") -> BinMapper:
    """Identical bin boundaries on every host: each host reservoir- or
    choice-samples its LOCAL shard, the samples are allgathered, and
    every host fits the SAME mapper on the gathered rows — the
    distributed BinMapper agreement LightGBM reaches inside its native
    allreduce ring (ref: TrainUtils.scala:207 LGBM_NetworkInit +
    LGBM_DatasetCreateFromMat). With ``bin_fit='sketch'`` hosts instead
    exchange mergeable quantile-sketch summaries built over ALL their
    rows (``_multihost_sketch_mapper``) — no row ever crosses hosts."""
    from jax.experimental import multihost_utils
    from mmlspark_tpu.core.sparse import CSRMatrix
    if bin_fit == "sketch":
        return _multihost_sketch_mapper(X, streaming, max_bin, nproc)
    cap = max(1000, _RESERVOIR_CAP // nproc)
    rng = np.random.default_rng(seed)
    if streaming:
        if not (isinstance(X, (list, tuple)) or callable(X)):
            raise ValueError(
                "multi-host streaming GBDT needs a replayable shard "
                "sequence (list or zero-arg factory), not a one-shot "
                "generator: bin boundaries must be agreed across hosts "
                "before any shard is binned")
        fac = X if callable(X) else (lambda: iter(X))
        sample = _reservoir_rows(
            ((np.asarray(s[0], np.float64),) for s in fac()), cap, seed)
    elif isinstance(X, CSRMatrix):
        # the gathered sample is dense — budget rows by bytes so wide
        # hashed features can't OOM before the binned-matrix guard runs
        cap = min(cap, max(100, (256 << 20) // (X.shape[1] * 8)))
        idx = rng.choice(X.shape[0], size=min(X.shape[0], cap),
                         replace=False)
        sample = X.take(idx).toarray().astype(np.float64)
    else:
        n_loc = len(X)
        idx = rng.choice(n_loc, size=min(n_loc, cap), replace=False)
        sample = np.asarray(X[idx] if isinstance(X, np.ndarray)
                            else np.asarray(X)[idx], dtype=np.float64)
    s_len = int(np.min(np.asarray(multihost_utils.process_allgather(
        np.asarray([len(sample)]))).ravel()))
    # f64 BIT-EXACT on the wire: the collective layer would silently
    # downcast float64 to f32 (jax x64 is off), so ship the raw bits as
    # uint32 pairs and reinterpret after the gather. An f32 wire would
    # let an f32-unsafe feature (timestamps, 2^24-scale IDs) bin
    # differently multi-host vs single-host — the exact failure class
    # the f64 host-binning work eliminated elsewhere.
    wire = np.ascontiguousarray(
        sample[:s_len], dtype=np.float64).view(np.uint32)
    gathered = np.ascontiguousarray(np.asarray(
        multihost_utils.process_allgather(wire)))
    gathered = gathered.reshape(-1, wire.shape[1]).view(np.float64)
    return BinMapper.fit(gathered, max_bin=max_bin,
                         sample_cnt=len(gathered), seed=seed)


def _bin_stream(shards, max_bin: int, seed: int,
                mapper: Optional[BinMapper] = None,
                bin_fit: str = "sample"):
    """Streaming ingestion: ``shards`` yields (X, y[, w]) tuples; only
    the int32 binned matrix is retained on host, so the raw floats never
    need to fit in RAM at once.

    Bin-boundary fidelity (LightGBM samples across the WHOLE dataset):
    replayable inputs (list/tuple or zero-arg factory) get a two-pass
    treatment — with ``bin_fit='sample'`` reservoir-sample all shards
    then fit; with ``bin_fit='sketch'`` run ``BinMapper.fit_streaming``
    so the mergeable quantile sketch sees EVERY row (boundaries within
    the sketch's measured rank-error certificate of an all-rows exact
    fit, instead of exact-on-a-200k-sample) — then bin. One-shot
    generators can only be binned with boundaries from the first shard;
    a reservoir accumulated alongside then MEASURES the drift a skewed
    shard order introduced and warns loudly when the first-shard
    boundaries disagree with full-stream boundaries."""
    replayable = isinstance(shards, (list, tuple)) or callable(shards)
    factory = (shards if callable(shards)
               else (lambda: iter(shards)) if replayable else None)

    forced = mapper is not None
    if forced:
        stream = factory() if replayable else shards
    elif replayable and bin_fit == "sketch":
        mapper = BinMapper.fit_streaming(
            (s[0] for s in factory()), max_bin=max_bin)
        stream = factory()
    elif replayable:
        sample = _reservoir_rows(factory(), _RESERVOIR_CAP, seed)
        mapper = BinMapper.fit(sample, max_bin=max_bin, seed=seed)
        stream = factory()
    else:
        stream = shards

    rng = np.random.default_rng(seed ^ 0x5EED)
    res_buf: Optional[np.ndarray] = None
    res_seen = 0
    first_shard_rows = 0
    bins_parts, y_parts, w_parts = [], [], []
    for shard in stream:
        Xs = np.asarray(shard[0], dtype=np.float64)
        ys = np.asarray(shard[1], dtype=np.float64)
        ws = (np.asarray(shard[2], dtype=np.float64) if len(shard) > 2
              else np.ones(len(ys)))
        if mapper is None:
            mapper = BinMapper.fit(Xs, max_bin=max_bin, seed=seed)
            first_shard_rows = len(Xs)
        if not replayable and not forced:
            # accumulate the full-stream reservoir for the drift check
            # (same fill/top-up/replace discipline as _reservoir_rows —
            # without the top-up the buffer would stay first-shard-sized
            # and the "full-stream" sample would bias to the tail)
            i = 0
            if res_buf is None:
                take = min(_RESERVOIR_CAP, len(Xs))
                res_buf, res_seen, i = Xs[:take].copy(), take, take
            elif len(res_buf) < _RESERVOIR_CAP:
                take = min(_RESERVOIR_CAP - len(res_buf), len(Xs))
                res_buf = np.concatenate([res_buf, Xs[:take]])
                res_seen += take
                i = take
            rest = Xs[i:]
            if len(rest):
                t = res_seen + np.arange(1, len(rest) + 1)
                accept = rng.random(len(rest)) < (_RESERVOIR_CAP / t)
                n_acc = int(accept.sum())
                if n_acc and len(res_buf) >= 1:
                    res_buf[rng.integers(0, len(res_buf), size=n_acc)] \
                        = rest[accept]
                res_seen += len(rest)
        bins_parts.append(mapper.transform(Xs))
        y_parts.append(ys)
        w_parts.append(ws)
    if mapper is None:
        raise ValueError("empty shard stream")
    if (not replayable and not forced and res_buf is not None
            and res_seen > first_shard_rows):
        # did the one-shot stream's first shard misrepresent the data?
        full_mapper = BinMapper.fit(res_buf, max_bin=max_bin, seed=seed)
        drift = float(np.mean(mapper.transform(res_buf)
                              != full_mapper.transform(res_buf)))
        if drift > 0.01:
            import logging
            logging.getLogger("mmlspark_tpu.gbdt").warning(
                "streaming binning drift: %.1f%% of sampled cells bin "
                "differently under first-shard vs full-stream "
                "boundaries — the shard order looks skewed/sorted. "
                "Pass a list or zero-arg factory of shards for exact "
                "two-pass quantiles.", 100 * drift)
    return (mapper, np.concatenate(bins_parts), np.concatenate(y_parts),
            np.concatenate(w_parts))


def comm_payload_model(parallel_mode: str, hist_comm: str,
                       hist_bits: int, num_trees: int, num_leaves: int,
                       num_features: int, num_bins: int, n_shards: int,
                       voting_k: int, num_rows: int) -> Dict[str, float]:
    """Per-device collective payload bytes for one training run,
    keyed by collective type ('psum' | 'psum_scatter' | 'all_gather').

    The collectives run inside the jitted boosting program, so bytes
    cannot be counted on the wire; this models the schedule exactly
    (the grow_tree collective sequence is static — the fori_loop always
    runs num_leaves-1 split steps) under the standard ring costs per
    device: allreduce 2*S*(D-1)/D, reduce-scatter S*(D-1)/D, all-gather
    S*(D-1)/D for an S-byte payload over D devices. Quantized runs
    (hist_bits < 32) ship int16 histogram wire (2 bytes/cell vs 4) plus
    one (3,) f32 scale psum per tree.
    """
    D = max(int(n_shards), 1)
    if D < 2 or num_trees <= 0:
        return {"psum": 0.0, "psum_scatter": 0.0, "all_gather": 0.0}
    ring = (D - 1) / D
    L, F, B = int(num_leaves), int(num_features), int(num_bins)
    item = 2 if hist_bits < 32 else 4        # histogram wire itemsize
    psum = scatter = gather = 0.0
    if parallel_mode == "data" and hist_comm == "reduce_scatter":
        fp = -(-F // D) * D                  # feature dim padded to D
        # per tree: L leaf histograms, each one reduce-scatter of the
        # (3, Fp, B) wire + one psum of the (3, B) feature-0 slice;
        # 2L-1 best_split calls each all_gather a (4,) f32 candidate
        scatter += L * (3 * fp * B * item) * ring
        psum += L * 2 * (3 * B * item) * ring
        gather += (2 * L - 1) * 16 * ring
    elif parallel_mode == "data":
        # per tree: L full-histogram allreduces (root + L-1 children)
        psum += L * 2 * (3 * F * B * item) * ring
    elif parallel_mode == "voting":
        k = min(max(int(voting_k), 1), F)
        c = D * k + 1                        # vote union + feature-0
        # per tree: 2L-1 top-k vote all_gathers; L single-slice psums
        # (root + right children) + L-1 UNSUBTRACTED pair psums (2x);
        # two (L,) f32 leaf-total psums
        gather += (2 * L - 1) * 4 * k * ring
        psum += (L + (L - 1) * 2) * 2 * (3 * c * B * item) * ring
        psum += 2 * 2 * (4 * L) * ring
    elif parallel_mode == "feature":
        # per tree: 2L-1 candidate all_gathers + L-1 row-indicator
        # broadcasts ((N,) f32 psum)
        gather += (2 * L - 1) * 16 * ring
        psum += (L - 1) * 2 * (4 * int(num_rows)) * ring
    if hist_bits < 32 and parallel_mode in ("data", "voting"):
        psum += 2 * 12 * ring                # (3,) f32 scales, per tree
    t = int(num_trees)
    return {"psum": psum * t, "psum_scatter": scatter * t,
            "all_gather": gather * t}


def resolve_hist_method(hist_method: str, backend: str,
                        max_bin: int) -> str:
    """Resolve the ``hist_method`` knob against the backend.

    'auto' picks the Pallas MXU kernel ONLY on TPU-class backends (the
    analog of the reference's native histogram loop,
    TrainUtils.scala:82-89); everywhere else it would run in slow
    interpret mode, so CPU/GPU fall back to the scatter (segment_sum)
    path. An explicit 'pallas' request beyond the kernel's VMEM tiling
    range (max_bin + 1 > 2048: the minimum block can't fit the one-hot
    budget) degrades to 'onehot' with a warning instead of failing
    Mosaic allocation."""
    if hist_method == "auto":
        hist_method = "pallas" if backend == "tpu" else "scatter"
    if hist_method == "pallas" and max_bin + 1 > 2048:
        import logging
        logging.getLogger("mmlspark_tpu.gbdt").warning(
            f"max_bin={max_bin} exceeds the Pallas kernel's VMEM "
            f"tiling range; using the onehot path")
        hist_method = "onehot"
    return hist_method


def _validate_hist_params(p: Dict[str, Any]) -> None:
    """Fail fast — an unsupported hist_bits/hist_comm combination must
    raise an actionable error, never silently run f32."""
    hist_bits = int(p["hist_bits"])
    if hist_bits not in (32, 16, 8):
        raise ValueError(
            f"hist_bits={p['hist_bits']} is not supported: use 32 "
            "(f32), 16 or 8 (quantized histograms)")
    if hist_bits < 32 and p["hist_method"] == "onehot":
        raise ValueError(
            f"hist_bits={hist_bits} is not supported by "
            "hist_method='onehot' (its einsum accumulates f32, so the "
            "run would silently lose the integer-exactness contract); "
            "use hist_method='scatter' (any backend) or 'pallas' "
            "(TPU), or hist_bits=32")
    if hist_bits == 16 and p["hist_method"] == "pallas":
        raise ValueError(
            "hist_bits=16 is not supported by hist_method='pallas' "
            "(what 'auto' resolves to on TPU): the MXU multiplies "
            "bf16 and int8 only, and Mosaic refuses the int16 matmul "
            "('Bad lhs/rhs type', compiled for TPU v5e); use "
            "hist_bits=8 or 32, or hist_method='scatter'")
    if hist_bits < 32 and p["parallelism"] == "feature":
        raise ValueError(
            "hist_bits < 32 with parallelism='feature' is not "
            "supported: feature-parallel histograms never cross the "
            "wire, so quantization only adds rounding noise; use "
            "parallelism='data' or 'voting', or hist_bits=32")
    if p["hist_comm"] == "auto":
        # quantized data-parallel gets the reduce-scatter partition
        # (the wire saving is the point); f32 keeps psum so the
        # default path stays bit-identical to the pre-reduce-scatter
        # engine on any device count
        p["hist_comm"] = ("reduce_scatter"
                          if hist_bits < 32
                          and p["parallelism"] == "data"
                          else "psum")
    elif p["hist_comm"] == "reduce_scatter":
        if p["parallelism"] != "data":
            raise ValueError(
                "hist_comm='reduce_scatter' requires "
                "parallelism='data' (feature/voting modes already "
                f"keep histograms local); got {p['parallelism']!r}")
    elif p["hist_comm"] != "psum":
        raise ValueError(
            f"unknown hist_comm={p['hist_comm']!r}; expected 'auto', "
            "'psum' or 'reduce_scatter'")


def train(params: Dict[str, Any], X, y: Optional[np.ndarray] = None,
          sample_weight: Optional[np.ndarray] = None,
          valid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
          feature_names: Optional[List[str]] = None,
          mesh: Optional[Mesh] = None,
          init_model: Optional["Booster | str"] = None,
          bin_mapper: Optional[BinMapper] = None) -> Booster:
    """Train a Booster. ``parallelism='data'`` shards rows over ``mesh``'s
    data axis and psums histograms (LightGBM data-parallel tree learner
    analog, ref: TrainParams.scala:26).

    ``X`` is either a dense (N, F) matrix with ``y`` labels, or — for
    datasets that should not be materialized as floats at once — an
    iterable of ``(X_shard, y_shard[, w_shard])`` tuples with ``y=None``
    (only the int32 binned matrix is kept per shard).

    ``init_model`` (Booster or model string) warm-starts boosting: the
    run continues from the given forest's scores and the returned
    Booster carries old + new trees (ref: TrainUtils.scala:74-77
    modelString warm start). Requires dense ``X`` (the base forest is
    scored on the raw features).

    ``bin_mapper`` overrides the bin-boundary fit with a FROZEN mapper
    (single-host only): the incremental-refresh path —
    ``Booster.boost_more(fresh_data)`` — bins new data against the
    original training distribution's cuts, so appended trees split in
    the same bin space as the base forest.

    The returned Booster carries ``train_timing``: per-phase wall
    seconds {bin, ship[, bin_device], first_iter (compile+first chunk),
    boost, fetch} so bench drift is attributable to a phase (host
    binning contention vs link bandwidth vs recompile vs device loop),
    and ``train_info``: {bin_path: 'device'|'host', boost_chunk,
    boost_chunks, bins_devices}."""
    import time as _time
    from mmlspark_tpu.core.trace import get_tracer
    _tracer = get_tracer()
    # one trace per train(): the phase marks below double as spans, so
    # the same bin/ship/boost intervals that feed the histograms are
    # readable per-run in /debug/traces and perfetto
    _trace = _tracer.new_trace("gbdt.train") if _tracer.enabled else None
    _phases: Dict[str, float] = {}
    _t_phase = _time.perf_counter()

    def _mark(name: str) -> None:
        nonlocal _t_phase
        now = _time.perf_counter()
        _phases[name] = _phases.get(name, 0.0) + (now - _t_phase)
        if _trace is not None:
            _tracer.emit(name, _t_phase, now, trace=_trace)
        _t_phase = now

    p = dict(DEFAULTS)
    p.update(params or {})
    p["hist_method"] = resolve_hist_method(
        p["hist_method"], jax.default_backend(), int(p["max_bin"]))
    _validate_hist_params(p)

    objective = get_objective(
        p["objective"], num_class=p["num_class"], alpha=p["alpha"],
        tweedie_variance_power=p["tweedie_variance_power"])
    K = objective.num_class

    # 1) bin on host, once (dense or streaming-shard input).
    # Streaming = an iterable of shards passed WITHOUT y; disambiguate
    # carefully so dense list-of-lists and mislabeled generators get a
    # clear error instead of a confusing unpack/object-cast failure.
    from mmlspark_tpu.core.sparse import CSRMatrix as _CSRMatrix
    from mmlspark_tpu.io.ooc import ChunkedTable as _ChunkedTable
    if isinstance(X, _ChunkedTable):
        # out-of-core ingest (io/ooc.py): chunks carry features+label
        # columns; adapt to the replayable (X, y) shard-factory shape.
        # Chunk decode runs on the source's prefetch worker.
        if y is not None:
            raise ValueError(
                "pass labels inside the ChunkedTable (label column), "
                "not as a separate y")
        X = X.as_xy()
    streaming = y is None and not isinstance(X, (np.ndarray, _CSRMatrix))
    if streaming and isinstance(X, (list, tuple)):
        try:
            X = np.asarray(X, dtype=np.float64)   # dense rows as lists
            streaming = False
        except (TypeError, ValueError):
            pass   # a genuine list of shard tuples / DataTables
    if not streaming and y is None:
        raise ValueError("y is required when X is a dense matrix")
    if y is not None and not isinstance(X, np.ndarray) \
            and hasattr(X, "__next__"):
        raise ValueError(
            "iterator X with a separate y is ambiguous: streaming mode "
            "passes y=None and the iterator yields "
            "(X_shard, y_shard[, w_shard]) tuples")
    # multi-host data-parallel: every process calls train() with its OWN
    # row shard; bin boundaries are agreed from allgathered samples and
    # the global binned matrix is assembled from per-process shards (the
    # LightGBM worker-partition flow, ref: TrainUtils.scala:188-214)
    from mmlspark_tpu.parallel import distributed as dist
    proc_info = dist.host_info()
    multi_host = (p["parallelism"] in ("data", "voting")
                  and proc_info.process_count > 1)
    # multi-host feature-parallel follows LightGBM's feature-parallel
    # data layout: EVERY worker holds the full dataset (rows replicated)
    # and owns a feature shard — LightGBM deliberately avoids the
    # split-partition broadcast this way (ref: TrainParams.scala:26
    # tree_learner=feature; docs "feature parallel ... every worker
    # holds the full data"). Each process therefore passes the same
    # full X; bin-boundary agreement is verified below.
    multi_host_fp = (p["parallelism"] == "feature"
                     and proc_info.process_count > 1)
    if multi_host_fp and streaming:
        raise ValueError(
            "multi-host tree_learner='feature' requires the full dense "
            "dataset on every process (LightGBM's feature-parallel "
            "layout); stream ingestion only supports "
            "parallelism='data'/'voting' across hosts")
    if p["parallelism"] == "serial" and proc_info.process_count > 1:
        import logging
        logging.getLogger("mmlspark_tpu.gbdt").warning(
            "train() called under %d jax processes with "
            "parallelism='serial': each host will fit an INDEPENDENT "
            "model on its local data. Use parallelism='data' for one "
            "globally-trained forest.", proc_info.process_count)
    forced_mapper = (_multihost_mapper(
        X, streaming, p["max_bin"], p["seed"], proc_info.process_count,
        bin_fit=p["bin_fit"])
        if multi_host else None)
    if bin_mapper is not None:
        if multi_host or multi_host_fp:
            raise ValueError(
                "bin_mapper override is single-host only (multi-host "
                "ingest agrees boundaries across processes itself)")
        forced_mapper = bin_mapper

    if streaming:
        if sample_weight is not None:
            raise ValueError(
                "pass per-shard weights inside the shard tuples in "
                "streaming mode")
        if init_model is not None:
            # fail fast — before consuming the (possibly huge) stream
            raise ValueError("init_model warm start requires dense X")
        mapper, bins_np, y, w_base = _bin_stream(
            X, p["max_bin"], p["seed"], mapper=forced_mapper,
            bin_fit=p["bin_fit"])
        n, f = bins_np.shape
    else:
        from mmlspark_tpu.core.sparse import CSRMatrix
        y = np.asarray(y, dtype=np.float64)
        if isinstance(X, CSRMatrix):
            # CSR ingestion: bin straight from the sparse structure —
            # the dense FLOAT matrix never exists (the
            # LGBM_DatasetCreateFromCSR analog, ref:
            # LightGBMUtils.scala:283-351). The engine's HBM layout is
            # still a dense (F, N) int bin matrix; guard its footprint.
            n, f = X.shape
            if f * n * 4 > 8 << 30:
                raise ValueError(
                    f"binned matrix for CSR input would need "
                    f"{f * n * 4 / 2**30:.1f} GB ({f} features x {n} "
                    f"rows); reduce the feature width (hashing) first")
            w_base = (np.ones(n) if sample_weight is None
                      else np.asarray(sample_weight, dtype=np.float64))
            mapper = forced_mapper or BinMapper.fit_sparse(
                X, max_bin=p["max_bin"], seed=p["seed"])
            # (F, N) natively; the .T view re-transposes to the row-major
            # shape the shared code expects and is undone at zero cost by
            # the ascontiguousarray(bins_np.T) below
            bins_np = mapper.transform_sparse(X).T
        else:
            # f32 input stays f32: the binning fast path widens values
            # per-compare (exact), so the 2x-size f64 matrix copy never
            # materializes for the common float32 dataset
            X = np.asarray(X)
            if X.dtype not in (np.float32, np.float64):
                X = X.astype(np.float64)
            n, f = X.shape
            w_base = (np.ones(n) if sample_weight is None
                      else np.asarray(sample_weight, dtype=np.float64))
            mapper = (forced_mapper or
                      BinMapper.fit(X, max_bin=p["max_bin"],
                                    seed=p["seed"]))
            bins_np = None   # dense path bins on device (below)
    if feature_names is None:
        feature_names = [f"Column_{i}" for i in range(f)]
    if bin_mapper is not None and len(mapper.num_bins) != f:
        raise ValueError(
            f"frozen bin_mapper covers {len(mapper.num_bins)} features, "
            f"X has {f}")
    num_bins = int(mapper.num_bins.max())
    if multi_host_fp:
        # every host fit its mapper on its own copy of the (supposedly
        # identical) full dataset — verify instead of trusting. The
        # digest covers shape, boundaries, labels/weights AND a strided
        # row sample of X itself: boundaries alone are row-ORDER
        # invariant, so a permuted copy would pass a boundary-only check
        # and then silently corrupt every split (the psum-broadcast row
        # bitmap is computed in the owner's row order)
        import hashlib
        from jax.experimental import multihost_utils
        h = hashlib.sha256()
        h.update(np.asarray([n, f], np.int64).tobytes())
        for u in mapper.upper_bounds:
            h.update(u.tobytes())
        h.update(np.ascontiguousarray(y).tobytes())
        h.update(np.ascontiguousarray(w_base).tobytes())
        from mmlspark_tpu.core.sparse import CSRMatrix as _CSRd
        if isinstance(X, _CSRd):
            # hash the CSR buffers — np.asarray(X) would densify the
            # whole matrix, the exact thing the sparse path forbids
            h.update(np.ascontiguousarray(X.indptr).tobytes())
            h.update(np.ascontiguousarray(X.indices).tobytes())
            h.update(np.ascontiguousarray(X.data).tobytes())
        else:
            stride = max(1, n // 1024)
            h.update(np.ascontiguousarray(
                np.asarray(X)[::stride]).tobytes())
        mine = np.frombuffer(h.digest(), np.uint8)
        alld = np.asarray(multihost_utils.process_allgather(mine))
        alld = alld.reshape(proc_info.process_count, -1)
        if not (alld == alld[0]).all():
            raise ValueError(
                "hosts disagree on the dataset (shape, bin boundaries, "
                "labels, or row content/order): multi-host "
                "tree_learner='feature' requires every process to pass "
                "the IDENTICAL full dataset (LightGBM feature-parallel "
                "layout)")

    # 2) parallel layout (tree_learner modes, ref: TrainParams.scala:26)
    # voting shards rows exactly like data-parallel; only the per-split
    # collective differs (tree.grow_tree best_split_voting)
    data_parallel = p["parallelism"] in ("data", "voting")
    feature_parallel = p["parallelism"] == "feature"
    axis_name = None
    n_shards = 1
    if data_parallel or feature_parallel:
        if mesh is None:
            mesh = mesh_lib.make_mesh()
        axis_name = mesh_lib.DATA_AXIS
        n_shards = mesh.shape[axis_name]

    if multi_host:
        # hosts truncate to the global-min LOCAL row count so every
        # process contributes an identically-shaped shard to the global
        # arrays (ragged shards would break make_array_from_process_
        # local_data and desynchronize the training loop)
        from jax.experimental import multihost_utils
        n_all = np.asarray(multihost_utils.process_allgather(
            np.asarray([n]))).ravel()
        n_min = int(n_all.min())
        if n_min != n:
            import logging
            logging.getLogger("mmlspark_tpu.gbdt").warning(
                "host shards are unequal (%s); truncating to %d rows "
                "per host", n_all.tolist(), n_min)
            y, w_base = y[:n_min], w_base[:n_min]
            if bins_np is not None:
                bins_np = bins_np[:n_min]
            if isinstance(X, np.ndarray):
                X = X[:n_min]
            else:
                from mmlspark_tpu.core.sparse import CSRMatrix as _C
                if isinstance(X, _C):
                    X = X[:n_min]   # warm-start scoring needs same rows
            n = n_min
        # pad LOCAL rows to this process's device count; the global
        # row count is then divisible by the full data axis
        pad = (-n) % max(len(jax.local_devices()), 1)
    else:
        # rows pad to the shard count only when rows are sharded
        pad = (-n) % max(n_shards if data_parallel else 1, 1)
    if pad:
        y_pad = np.pad(y, (0, pad))
        w_pad = np.pad(w_base, (0, pad))  # zero weight → padding inert
    else:
        y_pad, w_pad = y, w_base
    n_padded = n + pad
    # features-major (F, N) layout: per-split column reads become
    # contiguous rows and the Pallas kernel consumes it directly (see
    # tree.grow_tree docstring). Binning runs ON DEVICE when the mapper
    # is f32-safe (raw f32 blocks ship async, one jitted searchsorted
    # assigns bins — the host binning pass disappears entirely);
    # otherwise it happens on HOST (native OpenMP kernel or the
    # threaded numpy path; f64-exact for every feature scale) and the
    # NARROW bin matrix ships — at max_bin<=255 that is uint8, 4x fewer
    # bytes than the f32 feature matrix.
    # record f32 safety on the model so inference picks the right walk
    # (warm start below ORs in the base model's flag)
    p["f32_unsafe"] = not mapper.f32_safe()
    # feature-parallel shards the (F, N) feature dim: pad F to the shard
    # count with always-masked dummy features (fmask 0 keeps them out of
    # every split search)
    f_pad = (-f) % n_shards if feature_parallel else 0
    f_eff = f + f_pad
    # pipelined bin+ship (single-host): produce one feature CHUNK of the
    # (F, N) ship layout on the host while the previous chunk's
    # host->device DMA is in flight (device_put dispatch is async; only
    # the final concatenate waits). The two phases previously serialized
    # — HIGGS-1M paid bin 1.7s + ship 2.0s back to back; overlapped they
    # cost ~max of the two (ref: the reference's native path overlaps
    # per-partition dataset construction, TrainUtils.scala:19-64).
    # Dense input bins each chunk via transform_fm_range (native range
    # kernel when available, numpy fallback otherwise); pre-binned input
    # (streaming/CSR) transposes + narrows each column block while the
    # previous block flies. Multi-host keeps the one-shot numpy path —
    # its global array is assembled from per-process shards below.
    narrow = (np.uint8 if num_bins <= 256
              else np.int16 if num_bins <= 32767 else np.int32)
    # ~8 MB of rows per chunk amortizes per-transfer dispatch;
    # pipelining needs >= 2 chunks to overlap anything
    # (ship_chunk_bytes is a tuning/test knob, not a public param)
    chunk_bytes = int(p.get("ship_chunk_bytes", 8 << 20))
    chunk_f = max(1, chunk_bytes // max(n_padded, 1))
    # ON-DEVICE BINNING: when float32 compares provably reproduce the
    # f64 bin assignment (mapper.f32_safe — f32-snapped cuts for f32
    # input, gap+holdout certification otherwise), ship the RAW float32
    # feature blocks (overlapped async device_put per block, same shape
    # as the binned pipeline below) and bucketize on device with one
    # jitted vectorized searchsorted against the (F, B) bounds matrix.
    # Host binning — previously 43% of the HIGGS wall together with the
    # binned-matrix ship — collapses to a slice/cast staging pass plus
    # a ~100 ms device kernel. Host binning stays the fallback for
    # f32-unsafe mappers, CSR, streaming shards, and multi-host ingest.
    device_binning = str(p.get("device_binning", "auto"))
    # gate on f32_cuts_exact, NOT f32_safe: only f32-snapped cuts (f32
    # input) make the device f32 compare equal the host f64 compare for
    # EVERY row by construction. A margin+holdout-certified f64 mapper
    # is good enough for the f32 INFERENCE walk (residual risk on
    # unsampled rows is accepted there) but would let training bins
    # silently differ between device_binning='auto' and 'off'.
    use_device_bin = (device_binning != "off"
                      and bins_np is None
                      and not isinstance(X, _CSRMatrix)
                      and not (multi_host or multi_host_fp)
                      and mapper.f32_cuts_exact)
    if device_binning == "on" and not use_device_bin:
        import logging
        if multi_host or multi_host_fp:
            _reason = "multi-host ingest assembles per-process shards"
        elif bins_np is not None or isinstance(X, _CSRMatrix):
            _reason = "input is pre-binned/CSR/streaming"
        else:
            _reason = ("cuts are not f32-exact (pass float32 features "
                       "to enable on-device binning)")
        logging.getLogger("mmlspark_tpu.gbdt").warning(
            "device_binning='on' requested but ineligible (%s); binning "
            "on host", _reason)
    bin_path = "host"
    pipelined = False
    if use_device_bin:
        bin_path = "device"
        bounds_np = mapper.bounds_matrix(np.float32)
        # raw f32 rows are 4 bytes/cell (vs 1 for uint8 bins) — budget
        # the block width by bytes so each DMA stays ~chunk-bytes-sized
        chunk_f_raw = max(1, chunk_bytes // max(4 * n_padded, 1))
        parts = []
        for j0 in range(0, f, chunk_f_raw):
            j1 = min(f, j0 + chunk_f_raw)
            blk = np.ascontiguousarray(X[:, j0:j1], dtype=np.float32)
            # bucketize EACH block as its DMA lands (async dispatch) and
            # narrow to the bin dtype immediately: only bins stay
            # resident — device peak is one raw block + the bin matrix,
            # same footprint as the host-binning path (concatenating
            # the raw blocks first would hold 2x the raw matrix in HBM)
            parts.append(binning_lib.bucketize_fm_device(
                jnp.asarray(blk),
                jnp.asarray(bounds_np[j0:j1])).astype(narrow))
        _mark("bin")    # host staging: column slice + f32 cast only
        bins_dev = (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=0))
        del parts
        if pad or f_pad:
            bins_dev = jnp.pad(bins_dev, ((0, f_pad), (0, pad)))
        bins_dev = bins_dev.astype(jnp.int32)
        jax.block_until_ready(bins_dev)
        _mark("bin_device")   # raw DMA + searchsorted kernel, overlapped
        pipelined = True      # skip the host bin+ship paths below
    if not pipelined and not (multi_host or multi_host_fp) \
            and f > chunk_f:
        parts = []
        if bins_np is None and not isinstance(X, _CSRMatrix):
            # normalize ONCE: the native kernel needs contiguous input,
            # and a per-chunk ascontiguousarray of a non-contiguous X
            # would copy the full matrix K times
            X = np.ascontiguousarray(X)
        for j0 in range(0, f, chunk_f):
            j1 = min(f, j0 + chunk_f)
            if bins_np is None:
                part = mapper.transform_fm_range(X, j0, j1)
            else:
                part = np.ascontiguousarray(bins_np[:, j0:j1].T)
            part = part.astype(narrow, copy=False)
            if pad:
                part = np.pad(part, ((0, 0), (0, pad)))
            parts.append(jnp.asarray(part))    # async H2D per block
        if f_pad:
            parts.append(jnp.zeros((f_pad, n_padded), narrow))
        _mark("bin")   # host binning/layout (block DMAs still in flight)
        bins_dev = jnp.concatenate(parts, axis=0).astype(jnp.int32)
        pipelined = True
    if not pipelined:
        if bins_np is None:
            # dense path: fused native bin+transpose+narrow straight
            # into the (F, N) ship layout (uint8 when bins fit)
            bins_t = mapper.transform_fm(X)
            if pad or f_pad:
                bins_t = np.pad(bins_t, ((0, f_pad), (0, pad)))
        else:
            if pad:
                bins_np = np.pad(bins_np, ((0, pad), (0, 0)))
            bins_t = np.ascontiguousarray(bins_np.T)
            if f_pad:
                bins_t = np.pad(bins_t, ((0, f_pad), (0, 0)))
        _mark("bin")   # mapper fit + host binning + (F, N) layout
        if multi_host or multi_host_fp:
            # multi-host keeps numpy — the global array is assembled
            # from per-process shards (or served via callback) below
            bins_dev = bins_t.astype(np.int32)
        else:
            # narrow dtype crosses the host->device link; the widen
            # runs on device (eager asarray+astype — no per-call
            # retrace). copy=False: the fused native path already
            # produced uint8
            bins_dev = jnp.asarray(
                bins_t.astype(narrow, copy=False)).astype(jnp.int32)

    # 3) init scores — fresh start or warm start from a base forest
    base_model: Optional[Booster] = None
    if init_model is not None:
        base_model = (Booster.from_string(init_model)
                      if isinstance(init_model, str) else init_model)
        if base_model.num_class != K:
            raise ValueError(
                f"init_model has {base_model.num_class} classes, "
                f"objective expects {K}")
        if base_model.objective.name != objective.name:
            raise ValueError(
                f"init_model was trained with objective "
                f"{base_model.objective.name!r}; resuming as "
                f"{objective.name!r} would mix link spaces")
        if len(base_model.feature_names) != f:
            raise ValueError(
                f"init_model was trained on "
                f"{len(base_model.feature_names)} features, X has {f} "
                f"(out-of-range gathers would clamp silently)")
        init_score = base_model.init_score
        p["f32_unsafe"] = bool(p["f32_unsafe"]) or bool(
            base_model.params.get("f32_unsafe", False))
        # score + merge against the base model's EFFECTIVE forest: an
        # early-stopped base contributes only its best_iteration trees
        # (raw_score truncates the same way)
        base_eff_trees = base_model._resolve_iterations(None) * K
        base_scores = np.pad(_base_raw_kn(base_model, X, K),
                             ((0, 0), (0, pad)))
    elif p["boost_from_average"]:
        if multi_host:
            # the init score must agree across hosts (quantile/average
            # objectives need the GLOBAL label distribution)
            from jax.experimental import multihost_utils
            y_g = np.asarray(multihost_utils.process_allgather(
                np.ascontiguousarray(y, dtype=np.float32))).reshape(-1)
            w_g = np.asarray(multihost_utils.process_allgather(
                np.ascontiguousarray(w_base, dtype=np.float32))
            ).reshape(-1)
            init_score = objective.init_score(
                y_g.astype(np.float64), w_g.astype(np.float64))
        else:
            init_score = objective.init_score(y, w_base)
    else:
        init_score = np.zeros(K)

    gp = GrowParams(
        num_leaves=int(p["num_leaves"]), num_bins=num_bins,
        min_data_in_leaf=int(p["min_data_in_leaf"]),
        min_sum_hessian_in_leaf=float(p["min_sum_hessian_in_leaf"]),
        max_depth=int(p["max_depth"]),
        lambda_l1=float(p["lambda_l1"]), lambda_l2=float(p["lambda_l2"]),
        min_gain_to_split=float(p["min_gain_to_split"]),
        hist_method=p["hist_method"],
        voting_k=int(p["top_k"]),
        hist_bits=int(p["hist_bits"]),
        hist_comm=p["hist_comm"],
        # n_shards is only consulted by the reduce-scatter partition;
        # pinning it to 1 otherwise keeps every other config's jit key
        # (and compiled-executable cache) identical across mesh sizes
        n_shards=(n_shards if p["hist_comm"] == "reduce_scatter"
                  else 1))
    lr = float(p["learning_rate"])

    scores_np = (base_scores if base_model is not None
                 else np.broadcast_to(
                     np.asarray(init_score, np.float32)[:, None],
                     (K, n_padded)))
    if multi_host:
        # assemble GLOBAL arrays from each process's local shard — the
        # collective-mesh replacement for the reference's per-worker
        # native Dataset + socket ring (ref: TrainUtils.scala:188-214)
        col_sh = jax.sharding.NamedSharding(
            mesh, P(None, mesh_lib.DATA_AXIS))
        row_sh = jax.sharding.NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
        bins_d = jax.make_array_from_process_local_data(col_sh, bins_dev)
        y_d = jax.make_array_from_process_local_data(
            row_sh, np.asarray(y_pad, np.float32))
        scores = jax.make_array_from_process_local_data(
            col_sh, np.asarray(scores_np, np.float32))
    elif data_parallel:
        shard = mesh_lib.data_sharding(mesh)
        bins_d = jax.device_put(
            bins_dev,
            jax.sharding.NamedSharding(
                mesh, P(None, mesh_lib.DATA_AXIS)))   # rows on data axis
        y_d = jax.device_put(jnp.asarray(y_pad, jnp.float32), shard)
        scores = jax.device_put(
            jnp.asarray(scores_np, jnp.float32),
            jax.sharding.NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS)))
    elif feature_parallel:
        col_sh = jax.sharding.NamedSharding(
            mesh, P(mesh_lib.DATA_AXIS, None))   # FEATURES on axis
        repl = jax.sharding.NamedSharding(mesh, P())
        if multi_host_fp:
            # every host holds the full (F, N) matrix; serve each device
            # its feature-shard via callback (process-order assumptions
            # of make_array_from_process_local_data don't apply — the
            # callback answers whatever index a local device owns)
            bins_host = bins_dev
            y_host = np.asarray(y_pad, np.float32)
            sc_host = np.ascontiguousarray(scores_np, np.float32)
            bins_d = jax.make_array_from_callback(
                bins_host.shape, col_sh, lambda idx: bins_host[idx])
            y_d = jax.make_array_from_callback(
                y_host.shape, repl, lambda idx: y_host[idx])
            scores = jax.make_array_from_callback(
                sc_host.shape, repl, lambda idx: sc_host[idx])
        else:
            bins_d = jax.device_put(bins_dev, col_sh)
            y_d = jax.device_put(jnp.asarray(y_pad, jnp.float32), repl)
            scores = jax.device_put(
                jnp.asarray(scores_np, jnp.float32), repl)
    else:
        bins_d = bins_dev
        y_d = jnp.asarray(y_pad, jnp.float32)
        scores = jnp.asarray(scores_np, jnp.float32)
    jax.block_until_ready((bins_d, y_d, scores))
    _mark("ship")   # narrow host->device transfer + placement

    # validation state — device-resident; the held-out set is scored
    # through the *binned* feature view (same comparisons training uses)
    # so the loop never converts a tree to host. The only per-iteration
    # device sync is the scalar early-stopping loss read.
    esr = int(p["early_stopping_round"])
    use_valid = valid is not None and esr > 0
    if use_valid:
        from mmlspark_tpu.core.sparse import CSRMatrix as _CSR
        if isinstance(valid[0], _CSR):
            bins_v_np = mapper.transform_sparse(valid[0]).T \
                .astype(np.float32)
        else:
            bins_v_np = mapper.transform(
                np.asarray(valid[0], dtype=np.float64)).astype(np.float32)
        yv_np = np.asarray(valid[1], dtype=np.float32)
        if multi_host or multi_host_fp:
            # every host must pass IDENTICAL valid data; lift it (and
            # the running scores below) to replicated global arrays so
            # the per-iteration scoring ops run on the global mesh
            _repl = jax.sharding.NamedSharding(mesh, P())
            bins_v = jax.make_array_from_process_local_data(
                _repl, np.ascontiguousarray(bins_v_np))
            yv = jax.make_array_from_process_local_data(_repl, yv_np)
        else:
            bins_v = jnp.asarray(bins_v_np)
            yv = jnp.asarray(yv_np)
        if base_model is not None:
            v_scores_np = _base_raw_kn(
                base_model, np.asarray(valid[0], dtype=np.float64), K)
        else:
            v_scores_np = np.broadcast_to(
                np.asarray(init_score, np.float32)[:, None],
                (K, bins_v.shape[0]))
        if multi_host or multi_host_fp:
            v_scores = jax.make_array_from_process_local_data(
                _repl, np.ascontiguousarray(v_scores_np, np.float32))
        else:
            v_scores = jnp.asarray(v_scores_np, jnp.float32)
    best_loss = np.inf
    best_iter = -1
    esr_sync = max(1, min(esr, 8)) if esr > 0 else 1
    # one fixed walk length -> one predict_trees compile for the whole
    # run (leaves self-loop, extra steps are no-ops)
    valid_depth = int(p["max_depth"]) if int(p["max_depth"]) > 0 \
        else int(p["num_leaves"]) - 1

    n_iter = int(p["num_iterations"])
    # iteration-batching: fuse boost_chunk iterations into one jitted
    # lax.scan dispatch (the models/learner.py run_chunk shape). Auto
    # mode only engages for runs long enough that the extra
    # remainder-length compile amortizes. An explicit boost_chunk is
    # honored EXCEPT under early stopping, where every chunk is capped
    # at esr_sync so the async loss-read cadence (and best_iteration)
    # keeps its contract — train_info reports the effective length.
    S_cfg = int(p.get("boost_chunk", 0) or 0)
    if S_cfg <= 0:
        S_cfg = 8 if n_iter >= 16 else 1
    if use_valid:
        S_cfg = min(S_cfg, esr_sync)
    S_cfg = max(1, min(S_cfg, n_iter))
    M = 2 * int(p["num_leaves"]) - 1
    # power-of-two capacity bucket: the forest buffer shape feeds the
    # jitted step, so tying it exactly to num_iterations would recompile
    # for every distinct iteration count (buffers here are tiny)
    t_cap = max(64, 1 << (n_iter * K - 1).bit_length())
    # the whole forest lives on device: K trees are written per step at
    # a traced row offset, one device_get fetches everything at the end
    _f_dtypes = {"feature": jnp.int32, "bin_threshold": jnp.int32,
                 "threshold": jnp.float32, "left": jnp.int32,
                 "right": jnp.int32, "value": jnp.float32,
                 "is_leaf": jnp.bool_, "gain": jnp.float32,
                 "count": jnp.float32}
    # numpy buffers in multi-host mode: jit treats them as replicated
    # inputs on the global mesh (a committed local jnp array would not
    # be addressable across processes)
    _zeros = np.zeros if (multi_host or multi_host_fp) else jnp.zeros
    forest = Tree(**{fld: _zeros((t_cap, M), dt)
                     for fld, dt in _f_dtypes.items()})

    bag_active = p["bagging_fraction"] < 1.0 and p["bagging_freq"] > 0
    ff_active = p["feature_fraction"] < 1.0
    # bagging/feature-fraction masks are derived ON DEVICE inside the
    # chunk program (tree.sample_iteration_masks: fold_in(key, it) +
    # threshold-compare — deterministic, resume-safe, chunking-
    # invariant), so the host RNG + per-iteration mask upload that used
    # to force one dispatch per iteration is gone.
    bag_cfg = ((float(p["bagging_fraction"]), int(p["bagging_freq"]))
               if bag_active else None)
    ff_cfg = float(p["feature_fraction"]) if ff_active else None
    # the mask key is a RUNTIME input to the chunk program (raw uint32
    # PRNGKey data), so a seed sweep with bagging active reuses one
    # compiled executable instead of recompiling the heaviest program
    # in the engine per seed; pinned to 0 when no mask is active
    # (is-None checks, not truthiness: ff_cfg == 0.0 is falsy but DOES
    # sample masks, and must honor the user's seed); quantized training
    # derives its per-round stochastic-rounding keys from the same
    # runtime key, so it must honor the seed too
    mask_key = jax.random.PRNGKey(
        int(p["seed"])
        if (bag_cfg is not None or ff_cfg is not None
            or int(p["hist_bits"]) < 32) else 0)
    def _rows_global(w_np):
        if multi_host:
            return jax.make_array_from_process_local_data(
                jax.sharding.NamedSharding(mesh, P(mesh_lib.DATA_AXIS)),
                np.asarray(w_np, np.float32))
        if multi_host_fp:   # rows replicated on the global mesh
            w_host = np.asarray(w_np, np.float32)
            return jax.make_array_from_callback(
                w_host.shape, jax.sharding.NamedSharding(mesh, P()),
                lambda idx: w_host[idx])
        return _maybe_shard(jnp.asarray(w_np, jnp.float32), mesh,
                            data_parallel)

    w_d = _rows_global(w_pad)
    fmask_base = np.zeros(f_eff, np.float32)
    fmask_base[:f] = 1.0          # padded dummy features stay masked

    from mmlspark_tpu.core.metrics import (gbdt_comm_add,
                                           gbdt_train_histograms)
    boost_chunk_hist = gbdt_train_histograms().get("boost_chunk")
    obj_key = (p["objective"], K, float(p["alpha"]),
               float(p["tweedie_variance_power"]))
    parallel_mode = (p["parallelism"]
                     if p["parallelism"] in ("feature", "voting")
                     else "data")
    trees_done = 0
    n_chunks = 0
    it0 = 0
    stop = False
    # pending per-chunk device loss vectors, flushed at esr_sync
    # iteration boundaries. The point is cadence, not pure asynchrony:
    # the stop decision consumes losses at the SAME boundaries for
    # every chunk length, which is what makes best_iteration/num_trees
    # chunk-length-invariant (the parity suite asserts it). Chunks
    # shorter than esr_sync stay fully async until the boundary; when
    # S == esr_sync (the capped default) each flush blocks on the
    # chunk dispatched just above — the cadence the per-iteration loop
    # already paid. Worst case trains up to esr_sync-1 extra
    # iterations past the stop point; best_iteration stays exact
    # (extra trees are truncated at scoring time).
    pending_val: List[Tuple[int, int, Any]] = []
    pending_iters = 0
    while it0 < n_iter and not stop:
        S = min(S_cfg, n_iter - it0)
        chunk_fn = _make_chunk_step(
            obj_key, gp, lr, K, axis_name, mesh, parallel_mode, S,
            bag_cfg, ff_cfg, f, f_eff)
        t_chunk = _time.perf_counter()
        scores, forest = chunk_fn(bins_d, scores, y_d, w_d, fmask_base,
                                  forest, np.int32(it0), mask_key)
        n_chunks += 1
        trees_done = (it0 + S) * K
        if it0 == 0:
            jax.block_until_ready(scores)
            _mark("first_iter")   # compile (unless cached) + first chunk
        elif boost_chunk_hist is not None:
            # host dispatch wall per chunk AFTER the first: enqueue time
            # plus any back-pressure once the dispatch queue fills — NOT
            # device execution (blocking here would serialize the async
            # pipeline). The compile-bearing first chunk lands under
            # first_iter, not in this series.
            _t_chunk_end = _time.perf_counter()
            boost_chunk_hist.observe((_t_chunk_end - t_chunk) * 1e3)
            if _trace is not None:
                _tracer.emit("boost_chunk", t_chunk, _t_chunk_end,
                             trace=_trace,
                             attrs={"it0": int(it0), "length": int(S)})

        if use_valid:
            eval_fn = _make_valid_eval(obj_key, K, lr, S, valid_depth)
            v_scores, losses = eval_fn(forest, bins_v, yv, v_scores,
                                       np.int32(it0 * K))
            pending_val.append((it0, S, losses))
            pending_iters += S
            if pending_iters >= esr_sync or it0 + S >= n_iter:
                for c_it0, c_len, c_losses in pending_val:
                    arr = np.asarray(c_losses)
                    for j in range(c_len):
                        cur = float(arr[j])
                        if cur < best_loss - 1e-12:
                            best_loss, best_iter = cur, c_it0 + j + 1
                        elif c_it0 + j + 1 - best_iter >= esr:
                            stop = True
                            break
                    if stop:
                        break
                pending_val.clear()
                pending_iters = 0
        it0 += S

    jax.block_until_ready(scores)
    _mark("boost")   # chunks 2..n of the jitted loop
    if trees_done:
        # one device->host transfer for the whole forest
        host = jax.device_get(forest._asdict())
        stacked = {name: arr[:trees_done] for name, arr in host.items()}
        # bin threshold -> raw value threshold, one vectorized gather.
        # Stored in float64: f32 storage would quantize away split
        # resolution for large-magnitude features (the jitted predict
        # path casts down itself when that is safe)
        thr_lut = mapper.threshold_matrix(num_bins)          # (F, B)
        thr = thr_lut[stacked["feature"], stacked["bin_threshold"]]
        stacked["threshold"] = np.where(stacked["is_leaf"], 0.0, thr)
        stacked["value"] = stacked["value"] * lr  # bake shrinkage
        tree_depths = [
            _tree_depth({k: v[t] for k, v in stacked.items()})
            for t in range(stacked["feature"].shape[0])]
    else:
        stacked = {}
        tree_depths = []

    if base_model is not None and base_eff_trees > 0:
        base_trees = {key: v[:base_eff_trees]
                      for key, v in base_model.trees.items()}
        stacked = _concat_forests(base_trees, stacked)
        tree_depths = (list(base_model.tree_depths[:base_eff_trees])
                       + tree_depths)
        if best_iter > 0:
            best_iter += base_eff_trees // K
    booster = Booster(objective, stacked, init_score, K, feature_names, p,
                      best_iteration=best_iter if esr > 0 else -1,
                      tree_depths=tree_depths)
    _mark("fetch")   # forest D2H + threshold conversion
    booster.train_timing = {k: round(v, 3) for k, v in _phases.items()}
    booster.train_info = {"bin_path": bin_path, "boost_chunk": S_cfg,
                          "boost_chunks": n_chunks,
                          # local devices holding a piece of the binned
                          # matrix (1 unless rows or features shard)
                          "bins_devices": len(
                              {s.device for s in
                               bins_d.addressable_shards})}
    if axis_name is not None:
        comm = comm_payload_model(
            parallel_mode=parallel_mode, hist_comm=p["hist_comm"],
            hist_bits=int(p["hist_bits"]), num_trees=trees_done,
            num_leaves=int(p["num_leaves"]), num_features=f_eff,
            num_bins=num_bins, n_shards=n_shards,
            voting_k=int(p["top_k"]), num_rows=n_padded)
        for _coll, _nb in comm.items():
            if _nb:
                gbdt_comm_add(_coll, _nb)
        booster.train_info["comm_bytes"] = {
            k: round(v) for k, v in comm.items()}
    # the frozen mapper rides on the booster (in-memory only): the
    # continued-boosting path bins FRESH data against the original cuts
    booster.bin_mapper = mapper
    if (p.get("keep_training_data")
            and not (multi_host or multi_host_fp)
            and base_model is None and not use_valid):
        # exact-continuation state: everything the chunk loop consumes,
        # still device-resident. Restricted to the cases where
        # continuation is provably bit-identical to one longer run —
        # no warm-start base (its forest lives outside this buffer)
        # and no early stopping (a stopped run's scores include the
        # overshoot chunks).
        booster._resume = {
            "bins_d": bins_d, "y_d": y_d, "w_d": w_d,
            "scores": scores, "forest": forest,
            "fmask_base": fmask_base, "mask_key": mask_key,
            "it_done": it0, "t_cap": t_cap, "gp": gp, "lr": lr,
            "obj_key": obj_key, "parallel_mode": parallel_mode,
            "axis_name": axis_name, "mesh": mesh, "K": K,
            "f": f, "f_eff": f_eff, "num_bins": num_bins,
            "bag_cfg": bag_cfg, "ff_cfg": ff_cfg,
            "mapper": mapper, "init_score": init_score,
            "feature_names": feature_names, "consumed": False,
        }
    elif p.get("keep_training_data"):
        import logging
        logging.getLogger("mmlspark_tpu.gbdt").warning(
            "keep_training_data requested but continuation state is "
            "only retained for single-host runs without init_model or "
            "early stopping; boost_more(data=None) will be unavailable")
    hists = gbdt_train_histograms()
    for phase_name, secs in _phases.items():
        h = hists.get(phase_name)
        if h is not None:
            h.observe(secs * 1e3)
    if _trace is not None:
        _trace.root.set("bin_path", bin_path)
        _trace.root.set("boost_chunks", n_chunks)
        _trace.root.set("trees", trees_done)
        _tracer.finish(_trace)
    return booster


def _host_predict_trees(X: np.ndarray, trees: Dict[str, np.ndarray],
                        max_depth: int) -> np.ndarray:
    """float64 numpy tree walk — same semantics as predict_trees (leaves
    self-loop, NaN goes left) without the f32 cast. (T, N)."""
    t_count, n = trees["feature"].shape[0], X.shape[0]
    out = np.empty((t_count, n), np.float32)
    rows = np.arange(n)
    for t in range(t_count):
        feat, thr = trees["feature"][t], trees["threshold"][t]
        left, right = trees["left"][t], trees["right"][t]
        node = np.zeros(n, np.int64)
        for _ in range(max_depth):
            fv = X[rows, feat[node]]
            go_left = ~(fv > thr[node])        # NaN -> left, like binning
            node = np.where(go_left, left[node], right[node])
        out[t] = trees["value"][t][node]
    return out


def _base_raw_kn(base_model: Booster, X: np.ndarray, K: int) -> np.ndarray:
    """Base-forest raw margins as (K, N) float32 (warm-start init)."""
    raw = base_model.raw_score(X)
    if K == 1:
        raw = raw[None, :]
    return np.asarray(raw, dtype=np.float32)


def _pad_nodes(v: np.ndarray, m: int, key: str) -> np.ndarray:
    """Grow a (T, M) tree-array's node dim with inert self-loop leaves."""
    t, cur = v.shape
    if cur == m:
        return v
    pad = m - cur
    if key in ("left", "right"):
        idx = np.broadcast_to(np.arange(cur, m), (t, pad))
        return np.concatenate([v, idx.astype(v.dtype)], axis=1)
    if key == "is_leaf":
        return np.concatenate([v, np.ones((t, pad), v.dtype)], axis=1)
    return np.concatenate([v, np.zeros((t, pad), v.dtype)], axis=1)


def _concat_forests(a: Dict[str, np.ndarray],
                    b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Stack two stacked-tree dicts along T, padding node dims to match
    (warm start may use a different num_leaves than the base model)."""
    if not a:
        return b
    if not b:
        return a
    m = max(a["feature"].shape[1], b["feature"].shape[1])
    return {key: np.concatenate(
        [_pad_nodes(a[key], m, key), _pad_nodes(b[key], m, key)], axis=0)
        for key in b}


def _maybe_shard(arr, mesh, data_parallel):
    if not data_parallel:
        return arr
    return jax.device_put(arr, mesh_lib.data_sharding(mesh, arr.ndim))


def _tree_depth(tree_host: Dict[str, np.ndarray]) -> int:
    """Max root→leaf depth (host-side BFS over the flat arrays)."""
    left, right = tree_host["left"], tree_host["right"]
    is_leaf = tree_host["is_leaf"].astype(bool)
    depth = 0
    frontier = [(0, 0)]
    while frontier:
        node, d = frontier.pop()
        if is_leaf[node] or left[node] == node:
            depth = max(depth, d)
            continue
        frontier.append((int(left[node]), d + 1))
        frontier.append((int(right[node]), d + 1))
    return max(depth, 1)


@functools.lru_cache(maxsize=128)
def _make_chunk_step(obj_key: Tuple[str, int, float, float],
                     gp: GrowParams, lr: float, K: int,
                     axis_name: Optional[str], mesh: Optional[Mesh],
                     parallel_mode: str, chunk_len: int,
                     bag_cfg: Optional[Tuple[float, int]],
                     ff_cfg: Optional[float],
                     f_valid: int, f_total: int):
    """Build the iteration-batched jitted boosting chunk:
    ``chunk_len`` iterations of gradients → K trees → score update
    fused into one ``lax.scan`` device program (the same shape as
    run_chunk in models/learner.py) — ONE host dispatch per chunk
    instead of per iteration, with bagging / feature-fraction masks
    derived on device per iteration (tree.sample_iteration_masks).
    lru_cached by (config, chunk length) so repeated train() calls at
    the same shapes reuse the compiled executable — including the
    remainder-length chunk.

    ``parallel_mode`` picks the tree_learner sharding (ref:
    TrainParams.scala:26): 'data' shards rows over the mesh axis,
    'feature' shards the (F, N) binned matrix's FEATURE dim and
    replicates rows (see tree.grow_tree)."""
    name, num_class, alpha, rho = obj_key
    objective = get_objective(name, num_class=num_class, alpha=alpha,
                              tweedie_variance_power=rho)

    def chunk(bins, scores, y, w_base, fmask_base, forest, it0, key):
        """forest: Tree of (T_cap, M) buffers; iteration it's K trees
        are written at rows it*K..it*K+K-1 ON DEVICE — no per-iteration
        host transfer or stacking (one device_get fetches the whole
        forest after the loop). ``key`` is the raw uint32 PRNGKey for
        the sampling masks — a runtime input, so the executable is
        seed-independent."""
        TRACE_COUNTS["boost_chunk"] += 1   # trace-time side effect

        def one_iter(carry, s):
            scores, forest = carry
            it = it0 + s
            w, fmask = sample_iteration_masks(
                key, it, w_base, fmask_base, bag_cfg, ff_cfg,
                f_valid, f_total, axis_name, parallel_mode)
            score_in = scores[0] if K == 1 else scores
            grad, hess = objective.grad_hess(score_in, y)
            if K == 1:
                grad, hess = grad[None, :], hess[None, :]
            # per-round stochastic-rounding key: fold 3 (disjoint from
            # bagging=1 / feature-fraction=2), then the iteration and
            # the class — every (round, class) rounds independently and
            # reproducibly across topologies
            kq = (jax.random.fold_in(jax.random.fold_in(key, it), 3)
                  if gp.hist_bits < 32 else None)
            for k in range(K):
                tree, leaf_of_row, leaf_vals, _ = grow_tree(
                    bins, grad[k], hess[k], w, fmask, gp, axis_name,
                    parallel_mode,
                    None if kq is None else jax.random.fold_in(kq, k))
                scores = scores.at[k].add(lr * leaf_vals[leaf_of_row])
                forest = Tree(*[
                    getattr(forest, fld).at[it * K + k].set(
                        getattr(tree, fld))
                    for fld in Tree._fields])
            return (scores, forest), None

        (scores, forest), _ = lax.scan(
            one_iter, (scores, forest),
            jnp.arange(chunk_len, dtype=jnp.int32))
        return scores, forest

    if axis_name is None:
        return jax.jit(chunk, donate_argnums=(1, 5))

    d = mesh_lib.DATA_AXIS
    tree_spec = Tree(*([P()] * len(Tree._fields)))
    if parallel_mode == "feature":
        # features sharded, rows replicated; tree/scores replicated
        in_specs = (P(d, None), P(), P(), P(), P(d), tree_spec, P(),
                    P())
        out_specs = (P(), tree_spec)
    else:
        in_specs = (P(None, d), P(None, d), P(d), P(d), P(None),
                    tree_spec, P(), P())
        out_specs = (P(None, d), tree_spec)
    mapped = shard_map(
        chunk, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1, 5))


@functools.lru_cache(maxsize=128)
def _make_valid_eval(obj_key: Tuple[str, int, float, float], K: int,
                     lr: float, chunk_len: int, valid_depth: int):
    """One jitted dispatch scoring a whole chunk's trees on the
    validation set: slice the chunk's S*K forest rows, walk them once
    (predict_trees), then sequentially accumulate per-iteration scores
    and losses with a lax.scan whose f32 add order matches the
    per-iteration loop exactly — the (S,) loss vector stays on device
    for the async early-stopping read."""
    name, num_class, alpha, rho = obj_key
    objective = get_objective(name, num_class=num_class, alpha=alpha,
                              tweedie_variance_power=rho)

    def eval_chunk(forest, bins_v, yv, v_scores, row0):
        TRACE_COUNTS["valid_eval"] += 1   # trace-time side effect

        def sl(a):
            return lax.dynamic_slice_in_dim(a, row0, chunk_len * K,
                                            axis=0)
        tv = predict_trees(
            bins_v, sl(forest.feature),
            sl(forest.bin_threshold).astype(jnp.float32),
            sl(forest.left), sl(forest.right), sl(forest.value),
            max_depth=valid_depth)                  # (S*K, Nv)
        tv = tv.reshape(chunk_len, K, -1)

        def body(vs, s):
            vs = vs + lr * tv[s]
            return vs, objective.loss(vs[0] if K == 1 else vs, yv)

        v_scores, losses = lax.scan(
            body, v_scores, jnp.arange(chunk_len, dtype=jnp.int32))
        return v_scores, losses

    return jax.jit(eval_chunk, donate_argnums=(3,))
