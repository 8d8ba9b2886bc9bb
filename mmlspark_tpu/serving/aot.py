"""AOT-compiled serving executables: kill the replica cold-start.

Every fleet replica used to pay per-bucket JIT tracing at process start
(``warmup()`` — trace + XLA-compile one program per pow-2 shape bucket)
before it could serve its first request. This module moves that work to
**model-export time**: ``export_model`` lowers and compiles every
(bucket, program) pair once, serializes the StableHLO executables
(``jax.export``) plus the weights/scales to a versioned artifact
directory, and seeds a persistent XLA compilation cache next to them —
so ``load_model`` on a fresh replica is *deserialize and go*: no Python
tracing of the model, and the XLA compile of each deserialized program
is a disk hit. Floor-pinned in ``tests/test_perf_floors.py``.

Artifact layout (``<dir>/``)::

    manifest.json        # kind, version, precision, buckets, backend,
                         # jax version, serve hints — human-readable
    programs.pkl         # [(key, input avals, serialized executable)]
    weights.pkl          # np weights pytree as the programs consume
                         # it (incl. int8 scales)
    model_fn.pkl         # lazy fallback for shapes the artifact
                         # never saw (tpu_model kind only)
    pipeline.pkl         # the fitted stage list (pipeline kind only)
    example.pkl          # warmup/calibration example rows
    example_request.json # one ready-to-POST request body
    xla_cache/           # persistent compilation cache, seeded at
                         # export with the LOAD-side compiles

Two artifact kinds:

- ``tpu_model`` — a ``TPUModel`` (f32 or int8-quantized): one exported
  program per bucket. ``load_model`` returns an ``AOTTPUModel`` whose
  compiled-call dispatch hits the pre-compiled executable by input
  signature — **zero JIT traces at request time**; an unseen shape
  falls back to jit (lazily unpickling the model fn) and counts a
  ``jit_cache_miss`` like any other recompile.
- ``pipeline`` — a fitted ``PipelineModel``/``FusedPipelineModel``
  served through the fused scorer: one exported program per
  (bucket, fused segment) of the SERVING plan. ``load_model`` rebuilds
  the fused pipeline and installs the executables on its segments.

AOT programs are **single-device** (one replica = one chip; the fleet
replicates — mesh-sharded serving is the separate ROADMAP item), and
``precision``/``aot`` ride the manifest into ``serving_model_info`` so
a rolling swap to an AOT/int8 replica is auditable on /metrics.

The format field records ``jax_export`` when ``jax.export`` is
available; otherwise export falls back to ``trace_cache`` — no
serialized programs, but the artifact's seeded compilation cache still
turns the load-side compiles into disk hits while tracing re-runs.
Everything here imports jax lazily so the cold-start runner
(``python -m mmlspark_tpu.serving.aot``) can stamp its clock before
paying the import.
"""

from __future__ import annotations

import contextlib
import json
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

ARTIFACT_VERSION = 1
FORMAT_JAX_EXPORT = "jax_export"
FORMAT_TRACE_CACHE = "trace_cache"

_MANIFEST = "manifest.json"
_PROGRAMS = "programs.pkl"
_WEIGHTS = "weights.pkl"
_MODEL_FN = "model_fn.pkl"
_PIPELINE = "pipeline.pkl"
_EXAMPLE = "example.pkl"
_EXAMPLE_REQUEST = "example_request.json"
_XLA_CACHE = "xla_cache"
# sharded artifacts only: the mesh axes + PartitionSpecs the programs
# were exported with (jax.export carries shardings; the load side must
# rebuild the same mesh shape and place inputs to match)
_SHARDING = "sharding.pkl"


def _jax_export():
    """jax.export when this jax has it, else None (trace-cache mode)."""
    try:
        import jax.export as je
        if hasattr(je, "export") and hasattr(je, "deserialize"):
            return je
    except Exception:  # noqa: BLE001 — any import failure = unsupported
        pass
    return None


def input_signature(inputs: Dict[str, Any]) -> Tuple:
    """Shape/dtype signature of a named-array dict — the key the
    per-bucket executables dispatch on (sorted, so env/feed dict
    ordering can never alias two programs)."""
    return tuple(sorted((k, tuple(np.shape(v)), str(np.asarray(v).dtype)
                         if not hasattr(v, "dtype") else str(v.dtype))
                        for k, v in inputs.items()))


def _avals_of(tree):
    """Pytree of arrays -> picklable pytree of (shape, dtype-str)."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), tree)


def _is_aval_leaf(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple))


def _avals_to_structs(tree, shardings=None):
    """The inverse: (shape, dtype) leaves -> ShapeDtypeStruct leaves.
    ``shardings`` (a single Sharding applied to every leaf, or a
    matching pytree) attaches the placement — sharded programs must be
    lowered against sharding-carrying avals or jax.export resolves a
    1-device context and refuses the multi-device call."""
    import jax
    if shardings is None:
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf[0], np.dtype(leaf[1])),
            tree, is_leaf=_is_aval_leaf)
    from jax.sharding import Sharding
    if isinstance(shardings, Sharding):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf[0], np.dtype(leaf[1]), sharding=shardings),
            tree, is_leaf=_is_aval_leaf)
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            leaf[0], np.dtype(leaf[1]), sharding=s),
        tree, shardings, is_leaf=_is_aval_leaf)


def _model_sharding_blob(model) -> Optional[Dict[str, Any]]:
    """The picklable description of a TPUModel's sharding (None when
    unsharded): mesh axes + the PartitionSpec trees. Device handles
    never enter the artifact — the load side rebuilds the mesh from
    its own processes' devices."""
    sh = getattr(model, "_sharding", None) or \
        getattr(model, "sharding", None)
    if not isinstance(sh, dict):
        return None
    return {
        "kind": "tpu_model",
        "axes": {str(k): int(v) for k, v in sh["mesh"].shape.items()},
        "weight_specs": sh["weight_specs"],
        "in_spec": sh["in_spec"],
        "out_spec": sh["out_spec"],
    }


def _load_sharding_blob(art_dir: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(art_dir, _SHARDING)
    if not os.path.exists(path):
        return None
    with open(path, "rb") as f:
        return pickle.load(f)


def _rebuild_mesh(axes: Dict[str, int]):
    from mmlspark_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh({k: int(v) for k, v in axes.items()})


@contextlib.contextmanager
def _artifact_cache(art_dir: str):
    """Point jax's persistent compilation cache into the artifact for
    the duration (export seeds it; load hits it), restoring the
    caller's cache config after. When the operator placed the cache
    (``JAX_COMPILATION_CACHE_DIR``) it stays where they put it and
    nothing is redirected. An artifact on a read-only mount (cache
    READS still work) still loads, just without (re)seeding the disk
    cache.

    NOTE: the cache redirection is process-global for the duration, so
    a compile racing on another thread during this window caches into
    the artifact instead of the configured dir (harmless but
    surprising). Load artifacts BEFORE initiating a swap on a live
    engine rather than from inside a serving callback."""
    from mmlspark_tpu.utils.compile_cache import cache_dir_from_env
    if cache_dir_from_env():
        yield
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    cache_dir = os.path.join(art_dir, _XLA_CACHE)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError:
        # read-only artifact with no pre-seeded cache dir: compile
        # without the disk cache rather than failing the load
        if not os.path.isdir(cache_dir):
            yield
            return
    knobs = {"jax_compilation_cache_dir": cache_dir,
             "jax_persistent_cache_min_entry_size_bytes": -1,
             "jax_persistent_cache_min_compile_time_secs": 0.0}
    old = {k: getattr(jax.config, k) for k in knobs}
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        # drop the lazily-initialized cache singleton so the dir change
        # takes effect mid-process
        compilation_cache.reset_cache()
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _single_device_mesh():
    import jax
    from mmlspark_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh({"data": 1}, devices=[jax.devices()[0]])


def _write_manifest(out_dir: str, manifest: Dict[str, Any]) -> None:
    with open(os.path.join(out_dir, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)


def read_manifest(art_dir: str) -> Dict[str, Any]:
    with open(os.path.join(art_dir, _MANIFEST)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


def export_model(model, example, out_dir: str, version: str = "v0",
                 ) -> Dict[str, Any]:
    """Export ``model`` + every (bucket, program) pair to ``out_dir``.

    ``model`` is a ``TPUModel`` (f32 or ``quantize()``d) or a fitted
    ``PipelineModel``/``FusedPipelineModel``; ``example`` is the same
    representative-row table/dict ``warmup`` takes. Returns the written
    manifest. Export compiles every program once (trace + XLA) — that
    is the point: replicas loading the artifact never do."""
    from mmlspark_tpu.core.fusion import FusedPipelineModel
    from mmlspark_tpu.core.stage import PipelineModel
    from mmlspark_tpu.models.tpu_model import TPUModel
    os.makedirs(out_dir, exist_ok=True)
    if isinstance(model, TPUModel):
        return _export_tpu_model(model, example, out_dir, version)
    if isinstance(model, (PipelineModel, FusedPipelineModel)):
        return _export_pipeline(model, example, out_dir, version)
    raise TypeError(
        f"cannot AOT-export {type(model).__name__}: expected TPUModel, "
        f"PipelineModel, or FusedPipelineModel")


class _CaptureRun:
    """Stand-in for a TPUModel's jitted forward during export: records
    every (weights, inputs) call so export sees EXACTLY the arrays the
    real transform path builds (coercion, padding, sharding, dtype
    casts included), while still computing through jit so transform's
    readback works. ``jitted`` is supplied by the caller so a SHARDED
    model's capture computes through the same explicit-shardings jit
    the live replica would."""

    def __init__(self, jitted: Callable):
        self.jitted = jitted
        self.calls: List[Tuple[Any, Dict[str, Any]]] = []

    def __call__(self, weights, inputs):
        self.calls.append((weights, inputs))
        return self.jitted(weights, inputs)


def _export_tpu_model(model, example, out_dir: str,
                      version: str) -> Dict[str, Any]:
    import jax
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    je = _jax_export()
    table = example if isinstance(example, DataTable) \
        else DataTable(dict(example))
    if len(table) == 0:
        raise ValueError("export needs at least one example row")

    # export clone: a SINGLE-device mesh for plain models (one replica
    # = one chip; the fleet replicates), or the model's OWN mesh
    # sharding for sharded models — jax.export carries the declared
    # shardings, so a multi-chip replica loads the artifact and serves
    # from its mesh with zero traces, exactly like a single-chip one
    sharding_blob = _model_sharding_blob(model)
    clone = TPUModel(modelFn=model.get("modelFn"),
                     weights=model.get("weights"),
                     feedDict=model.get("feedDict"),
                     fetchDict=model.get("fetchDict"),
                     batchSize=model.get("batchSize"),
                     computeDtype=model.get("computeDtype"),
                     inputCol=model.get("inputCol"),
                     outputCol=model.get("outputCol"),
                     precision=model.get("precision"))
    if sharding_blob is not None:
        clone.set_sharding(_rebuild_mesh(sharding_blob["axes"]),
                           weight_specs=sharding_blob["weight_specs"],
                           in_spec=sharding_blob["in_spec"],
                           out_spec=sharding_blob["out_spec"])
    else:
        clone.set_mesh(_single_device_mesh())

    model_fn = clone.get("modelFn")

    def run(weights, inputs):
        out = model_fn(weights, inputs)
        if not isinstance(out, dict):
            out = {"output": out}
        return out

    def make_jit():
        if sharding_blob is not None:
            return clone._jit_sharded(run, donate=())
        return jax.jit(run)

    def load_shardings(rec):
        """The (weights, inputs) sharding trees the LOAD side lowers
        against (None/None when unsharded)."""
        if sharding_blob is None:
            return None, None
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = clone._sharding["mesh"]
        w_sh = jax.tree_util.tree_map(
            lambda _leaf, s: NamedSharding(mesh, s),
            rec["weights_avals"], sharding_blob["weight_specs"],
            is_leaf=_is_aval_leaf)
        return w_sh, NamedSharding(mesh, sharding_blob["in_spec"])

    capture = _CaptureRun(make_jit())
    clone._jitted["run"] = capture      # transform uses it verbatim
    records: List[Dict[str, Any]] = []
    with _artifact_cache(out_dir):
        for b in clone.bucket_sizes():
            idx = np.resize(np.arange(len(table)), b)
            clone.transform(table._take_indices(idx))
        seen = set()
        for weights_dev, inputs in capture.calls:
            sig = input_signature(inputs)
            if sig in seen:
                continue
            seen.add(sig)
            rec = {"key": sig, "weights_avals": _avals_of(weights_dev),
                   "inputs_avals": _avals_of(inputs)}
            if je is not None:
                exp = je.export(make_jit())(weights_dev, inputs)
                rec["blob"] = exp.serialize()
                # seed the cache with the LOAD-side compile (the
                # deserialized module's HLO differs from the jit
                # trace's, so the load path needs its own entry)
                w_sh, in_sh = load_shardings(rec)
                jax.jit(je.deserialize(rec["blob"]).call).lower(
                    _avals_to_structs(rec["weights_avals"], w_sh),
                    _avals_to_structs(rec["inputs_avals"],
                                      in_sh)).compile()
            records.append(rec)

    with open(os.path.join(out_dir, _PROGRAMS), "wb") as f:
        pickle.dump(records, f)
    # the weights as the exported programs consume them: the clone's
    # placed tree, each leaf in the dtype the model fn reads it in
    # (TPUModel._weights_on_device) — a replica loads them and goes,
    # with no model fn to read a placement from
    host_weights = jax.tree_util.tree_map(np.asarray,
                                          clone._weights_on_device())
    with open(os.path.join(out_dir, _WEIGHTS), "wb") as f:
        pickle.dump(host_weights, f)
    with open(os.path.join(out_dir, _MODEL_FN), "wb") as f:
        pickle.dump(model.get("modelFn"), f)
    example_cols = {c: np.asarray(table[c][:1]).tolist()
                    if isinstance(table[c], np.ndarray)
                    else list(table[c][:1]) for c in table.column_names}
    with open(os.path.join(out_dir, _EXAMPLE), "wb") as f:
        pickle.dump(example_cols, f)
    field = list(clone._feeds().values())[0]
    req = {field: np.asarray(table[field][:1]).ravel().tolist()}
    with open(os.path.join(out_dir, _EXAMPLE_REQUEST), "w") as f:
        json.dump(req, f)
    manifest = {
        "artifact_version": ARTIFACT_VERSION,
        "kind": "tpu_model",
        "format": FORMAT_JAX_EXPORT if je is not None
        else FORMAT_TRACE_CACHE,
        "version": version,
        "precision": model.get("precision"),
        "buckets": clone.bucket_sizes(),
        "programs": len(records),
        "batch_size": int(model.get("batchSize")),
        "compute_dtype": model.get("computeDtype"),
        "int_input": bool(getattr(model.get("modelFn"), "int_input",
                                  False)),
        "feeds": clone._feeds(),
        "fetches": clone._fetches(),
        "serve": {"field": field},
        "backend": _backend(),
        "jax_version": _jax_version(),
    }
    if sharding_blob is not None:
        manifest["sharded"] = True
        manifest["mesh"] = sharding_blob["axes"]
        with open(os.path.join(out_dir, _SHARDING), "wb") as f:
            pickle.dump(sharding_blob, f)
    _write_manifest(out_dir, manifest)
    return manifest


def _segment_shardings(seg):
    """A sharded FusedSegment's (consts, env) in-sharding trees — the
    same placement ``FusedSegment._jit_sharded`` declares."""
    sh = seg.sharding
    return ([sh.const_sharding(op.name) for op in seg.ops],
            sh.env_sharding())


def _segment_record_shardings(seg, rec):
    """The sharding trees a record's avals lower against at LOAD time
    (None/None for unsharded segments). Prefix shardings expand to
    full trees so ``_avals_to_structs`` can zip leaf-for-leaf."""
    import jax
    from jax.sharding import Sharding
    if seg.sharding is None:
        return None, None
    consts_in, env_sh = _segment_shardings(seg)
    full = []
    for sh_i, avals_i in zip(consts_in, rec["consts_avals"]):
        if isinstance(sh_i, Sharding):
            full.append(jax.tree_util.tree_map(
                lambda _leaf, _s=sh_i: _s, avals_i,
                is_leaf=_is_aval_leaf))
        else:
            full.append(sh_i)
    return full, env_sh


@contextlib.contextmanager
def _capture_segment_calls():
    """Export-time hook: wrap ``FusedSegment.compiled`` so every fused
    dispatch records (segment, consts, env) — the exact arrays the
    serving path builds (bucket padding included)."""
    from mmlspark_tpu.core import fusion as FZ
    orig = FZ.FusedSegment.compiled
    calls: List[Tuple[Any, Any, Dict[str, Any]]] = []

    def wrapper(self, donate):
        real = orig(self, donate)

        def capture(consts, env):
            calls.append((self, consts, env))
            return real(consts, env)

        return capture

    FZ.FusedSegment.compiled = wrapper
    try:
        yield calls
    finally:
        FZ.FusedSegment.compiled = orig


def _export_pipeline(pipeline, example, out_dir: str,
                     version: str) -> Dict[str, Any]:
    import jax
    from mmlspark_tpu.core.fusion import FusedPipelineModel
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.serving.fleet import _FusedPipelineScorer
    je = _jax_export()
    fused = pipeline if isinstance(pipeline, FusedPipelineModel) \
        else pipeline.fused()
    table = example if isinstance(example, DataTable) \
        else DataTable(dict(example))
    if len(table) == 0:
        raise ValueError("export needs at least one example row")
    scorer = _FusedPipelineScorer(fused, batch_size=fused.batch_size)

    with _artifact_cache(out_dir), _capture_segment_calls() as calls:
        scorer.warmup(table)
        if not calls:
            raise ValueError(
                "nothing to AOT-export: the serving plan has no fused "
                "segment (host-only pipelines have no compiled programs "
                "to serialize)")
        # resolve each captured segment to its step index in the
        # serving plan (the plan load_model will rebuild)
        plan = None
        for p in fused._plans.values():
            if any(step is calls[0][0] for step in p.steps):
                plan = p
                break
        if plan is None:
            raise RuntimeError("serving plan not found after warmup")
        records = []
        seen = set()
        for seg, consts, env in calls:
            step = next(i for i, s in enumerate(plan.steps) if s is seg)
            sig = seg.env_signature(env)
            if (step, sig) in seen:
                continue
            seen.add((step, sig))
            rec = {"step": step, "key": sig,
                   "consts_avals": _avals_of(consts),
                   "env_avals": _avals_of(env)}
            if je is not None:
                fn = seg._make_fn(count_traces=False)
                if seg.sharding is not None:
                    # mesh-sharded segment: export the same explicit-
                    # shardings program the live replica runs (the env
                    # arrays captured here are already placed per spec)
                    consts_in, env_sh = _segment_shardings(seg)
                    jitted = jax.jit(fn, in_shardings=(consts_in,
                                                       env_sh),
                                     out_shardings=env_sh)
                else:
                    jitted = jax.jit(fn)
                exp = je.export(jitted)(consts, env)
                rec["blob"] = exp.serialize()
                c_sh, e_sh = _segment_record_shardings(seg, rec)
                jax.jit(je.deserialize(rec["blob"]).call).lower(
                    _avals_to_structs(rec["consts_avals"], c_sh),
                    _avals_to_structs(rec["env_avals"], e_sh)).compile()
            records.append(rec)

    with open(os.path.join(out_dir, _PROGRAMS), "wb") as f:
        pickle.dump(records, f)
    with open(os.path.join(out_dir, _PIPELINE), "wb") as f:
        pickle.dump({"stages": fused.get_stages(),
                     "in_schema": plan.in_schema,
                     "final_needed": plan.final_needed,
                     "reply_col": scorer.reply_col,
                     "row_names": list(scorer._row_names)}, f)
    rows = [dict(zip(table.column_names,
                     (table[c][0] for c in table.column_names)))]
    with open(os.path.join(out_dir, _EXAMPLE), "wb") as f:
        pickle.dump({c: [table[c][0]] for c in table.column_names}, f)
    from mmlspark_tpu.io.http import _jsonable
    with open(os.path.join(out_dir, _EXAMPLE_REQUEST), "w") as f:
        json.dump({k: _jsonable(v) for k, v in rows[0].items()}, f)
    manifest = {
        "artifact_version": ARTIFACT_VERSION,
        "kind": "pipeline",
        "format": FORMAT_JAX_EXPORT if je is not None
        else FORMAT_TRACE_CACHE,
        "version": version,
        "precision": fused.precision,
        "buckets": fused.bucket_sizes(),
        "programs": len(records),
        "batch_size": int(fused.batch_size),
        "serve": {"reply_col": scorer.reply_col},
        "backend": _backend(),
        "jax_version": _jax_version(),
    }
    if fused.sharding is not None:
        sh = fused.sharding
        axes = {str(k): int(v) for k, v in sh.mesh.shape.items()}
        manifest["sharded"] = True
        manifest["mesh"] = axes
        with open(os.path.join(out_dir, _SHARDING), "wb") as f:
            pickle.dump({"kind": "pipeline", "axes": axes,
                         "data_axis": sh.data_axis,
                         "const_specs": sh.const_specs}, f)
    _write_manifest(out_dir, manifest)
    return manifest


def _backend() -> str:
    import jax
    return jax.default_backend()


def _jax_version() -> str:
    import jax
    return jax.__version__


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------


class _LazyModelFn:
    """Placeholder model fn on an AOT-loaded model: carries the traced
    model's ``int_input`` flag (the transform path needs it to coerce
    feeds) without unpickling — calling it means the lazy fallback
    failed to load."""

    def __init__(self, int_input: bool):
        self.int_input = bool(int_input)

    def __call__(self, *a, **kw):
        raise RuntimeError("AOT placeholder model fn invoked; the "
                           "fallback failed to load")


def _compile_record(rec, avals_args) -> Optional[Callable]:
    """One serialized program -> a callable executable (pre-compiled at
    load — the only XLA work a replica does, and a cache disk-hit when
    the artifact's seeded xla_cache rode along)."""
    import jax
    je = _jax_export()
    if "blob" not in rec or je is None:
        return None
    exp = je.deserialize(rec["blob"])
    return jax.jit(exp.call).lower(*avals_args).compile()


def load_model(art_dir: str):
    """Rebuild a served model from an AOT artifact: deserialize the
    pre-compiled (bucket, program) executables and return a model that
    serves with ZERO jit traces at request time. Returns an
    ``AOTTPUModel`` (tpu_model kind) or a ``FusedPipelineModel`` with
    AOT programs installed (pipeline kind); both carry
    ``aot=True`` + the artifact's ``precision`` for the
    serving_model_info labels, and slot straight into
    ``json_scoring_pipeline`` / ``ServingEngine.swap``."""
    manifest = read_manifest(art_dir)
    if manifest["kind"] == "tpu_model":
        return _load_tpu_model(art_dir, manifest)
    if manifest["kind"] == "pipeline":
        return _load_pipeline(art_dir, manifest)
    raise ValueError(f"unknown artifact kind {manifest['kind']!r}")


_AOT_MODEL_CLS = None


def _aot_model_class():
    """The AOTTPUModel class, built once on first load (TPUModel pulls
    in jax, which this module keeps out of import time)."""
    global _AOT_MODEL_CLS
    if _AOT_MODEL_CLS is not None:
        return _AOT_MODEL_CLS
    from mmlspark_tpu.models.tpu_model import (
        TPUModel, _Reading, _signature,
    )

    class AOTTPUModel(TPUModel):
        """TPUModel whose compiled-call dispatch goes straight to the
        artifact's pre-compiled executables (by input signature). The
        model fn is NOT loaded — an unseen shape lazily unpickles it,
        traces, and counts a jit_cache_miss like any recompile."""

        def _post_init(self):
            super()._post_init()
            self.aot = True
            self._aot_programs: Dict[Tuple, Callable] = {}
            self._artifact_dir: Optional[str] = None

        def _read(self, inputs):
            # the artifact's weights are stored as its programs consume
            # them: placed as held, with no model fn to trace
            if isinstance(self.get("modelFn"), _LazyModelFn):
                return _Reading(_signature(inputs), None, None, None)
            return super()._read(inputs)

        def _fallback(self) -> Callable:
            # check-then-set under the model's init lock: two workers
            # hitting unseen shapes at once must not both unpickle —
            # the second set("modelFn") would wipe _jitted and re-trace
            # every fallback shape the first already compiled. The jit
            # build itself happens in super()._compiled() OUTSIDE this
            # block (the lock is not reentrant).
            with self._init_lock:
                if isinstance(self.get("modelFn"), (_LazyModelFn,
                                                    type(None))):
                    path = os.path.join(self._artifact_dir, _MODEL_FN)
                    if not os.path.exists(path):
                        raise RuntimeError(
                            "AOT artifact has no model_fn fallback and "
                            "this input shape was never exported")
                    with open(path, "rb") as f:
                        self.set("modelFn", pickle.load(f))
            return super()._compiled()

        def _compiled(self) -> Callable:
            progs = self._aot_programs
            if not progs:
                return self._fallback()
            model = self

            def dispatch(weights, inputs):
                prog = progs.get(input_signature(inputs))
                if prog is not None:
                    return prog(weights, inputs)
                return model._fallback()(weights, inputs)

            return dispatch

    _AOT_MODEL_CLS = AOTTPUModel
    return AOTTPUModel


def _model_kwargs(manifest: Dict[str, Any],
                  weights: Any) -> Dict[str, Any]:
    """The ONE manifest -> TPUModel constructor mapping, shared by
    ``load_model`` and the cold-start runner's trace-mode rebuild so
    the two replicas being compared are configured identically."""
    return dict(
        weights=weights, batchSize=manifest["batch_size"],
        computeDtype=manifest.get("compute_dtype", "float32"),
        feedDict=manifest.get("feeds"),
        fetchDict=manifest.get("fetches"),
        inputCol=manifest["serve"]["field"],
        outputCol=list(manifest["fetches"])[0],
        precision=manifest.get("precision", "f32"))


def _load_tpu_model(art_dir: str, manifest: Dict[str, Any]):
    with open(os.path.join(art_dir, _WEIGHTS), "rb") as f:
        weights = pickle.load(f)
    with open(os.path.join(art_dir, _PROGRAMS), "rb") as f:
        records = pickle.load(f)
    model = _aot_model_class()(
        modelFn=_LazyModelFn(manifest.get("int_input", False)),
        **_model_kwargs(manifest, weights))
    model._artifact_dir = art_dir
    sharding_blob = _load_sharding_blob(art_dir) \
        if manifest.get("sharded") else None
    w_sh = in_sh = None
    if sharding_blob is not None:
        # the multi-chip replica: same mesh shape, this process's
        # devices; the unseen-shape jit fallback is sharded too
        mesh = _rebuild_mesh(sharding_blob["axes"])
        model.set_sharding(mesh,
                           weight_specs=sharding_blob["weight_specs"],
                           in_spec=sharding_blob["in_spec"],
                           out_spec=sharding_blob["out_spec"])
        import jax
        from jax.sharding import NamedSharding
        in_sh = NamedSharding(mesh, sharding_blob["in_spec"])
    else:
        model.set_mesh(_single_device_mesh())
    with _artifact_cache(art_dir):
        for rec in records:
            if sharding_blob is not None and w_sh is None:
                # one NamedSharding tree serves every record: the
                # specs and mesh never change between buckets
                w_sh = jax.tree_util.tree_map(
                    lambda _leaf, s: NamedSharding(mesh, s),
                    rec["weights_avals"],
                    sharding_blob["weight_specs"],
                    is_leaf=_is_aval_leaf)
            co = _compile_record(
                rec, (_avals_to_structs(rec["weights_avals"], w_sh),
                      _avals_to_structs(rec["inputs_avals"], in_sh)))
            if co is not None:
                model._aot_programs[tuple(map(tuple, rec["key"]))] = co
    if not model._aot_programs:
        # trace-cache format: programs re-trace through the fallback,
        # but compiles hit the artifact's seeded cache. Load the fn
        # eagerly and warm every bucket here (still off the hot path).
        model._fallback()
        with open(os.path.join(art_dir, _EXAMPLE), "rb") as f:
            example = pickle.load(f)
        with _artifact_cache(art_dir):
            model.warmup(example)
    return model


def _load_pipeline(art_dir: str, manifest: Dict[str, Any]):
    from mmlspark_tpu.core.fusion import FusedPipelineModel, FusedSegment
    with open(os.path.join(art_dir, _PIPELINE), "rb") as f:
        meta = pickle.load(f)
    with open(os.path.join(art_dir, _PROGRAMS), "rb") as f:
        records = pickle.load(f)
    fused = FusedPipelineModel(meta["stages"],
                               batch_size=manifest["batch_size"])
    sharding_blob = _load_sharding_blob(art_dir) \
        if manifest.get("sharded") else None
    if sharding_blob is not None:
        fused.shard(_rebuild_mesh(sharding_blob["axes"]),
                    data_axis=sharding_blob["data_axis"],
                    const_specs=sharding_blob.get("const_specs"))
    plan = fused.plan_for(meta["in_schema"], meta["final_needed"])
    with _artifact_cache(art_dir):
        for rec in records:
            step = plan.steps[rec["step"]]
            if not isinstance(step, FusedSegment):
                raise RuntimeError(
                    f"artifact step {rec['step']} is not a fused segment"
                    f" in the rebuilt plan — stage list drifted")
            c_sh, e_sh = _segment_record_shardings(step, rec)
            co = _compile_record(
                rec, (_avals_to_structs(rec["consts_avals"], c_sh),
                      _avals_to_structs(rec["env_avals"], e_sh)))
            if co is not None:
                step.install_aot({tuple(map(tuple, rec["key"])): co})
    fused.aot = True
    return fused


# ---------------------------------------------------------------------------
# cold-start runner (bench + floor test drive this as a fresh process)
# ---------------------------------------------------------------------------


def _force_mesh_devices(manifest: Dict[str, Any]) -> None:
    """A sharded artifact needs as many devices as its export mesh.
    On a CPU host (tests/bench: the forced-host-device-count recipe)
    give this process enough VIRTUAL cpu devices BEFORE first backend
    use; on a real accelerator the topology is what it is and a
    mismatch surfaces as jax.export's own count error."""
    mesh = manifest.get("mesh")
    if not mesh:
        return
    import math
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if "cpu" not in platforms.split(","):
        return
    from mmlspark_tpu.utils.jax_compat import set_cpu_device_count
    set_cpu_device_count(math.prod(int(v) for v in mesh.values()))


def _coldstart(art_dir: str, mode: str, port: int,
               t0: float) -> Dict[str, Any]:
    """Build a serving replica from the artifact and time process-start
    -> first HTTP 200. ``mode='aot'`` loads the pre-compiled
    executables; ``mode='trace'`` rebuilds the model from weights +
    model fn and pays the per-bucket trace+compile warmup — today's
    trace-at-startup replica, the baseline the AOT path retires."""
    import urllib.request
    manifest = read_manifest(art_dir)
    _force_mesh_devices(manifest)
    if mode == "aot":
        model = load_model(art_dir)
    elif manifest["kind"] == "pipeline":
        from mmlspark_tpu.core.fusion import FusedPipelineModel
        with open(os.path.join(art_dir, _PIPELINE), "rb") as f:
            meta = pickle.load(f)
        model = FusedPipelineModel(meta["stages"],
                                   batch_size=manifest["batch_size"])
        blob = _load_sharding_blob(art_dir) \
            if manifest.get("sharded") else None
        if blob is not None:
            # the trace-mode baseline replica shards the same way the
            # AOT one does — the two cold starts being compared differ
            # ONLY in where the compiles come from
            model.shard(_rebuild_mesh(blob["axes"]),
                        data_axis=blob["data_axis"],
                        const_specs=blob.get("const_specs"))
    else:
        from mmlspark_tpu.models.tpu_model import TPUModel
        with open(os.path.join(art_dir, _WEIGHTS), "rb") as f:
            weights = pickle.load(f)
        with open(os.path.join(art_dir, _MODEL_FN), "rb") as f:
            model_fn = pickle.load(f)
        model = TPUModel(modelFn=model_fn,
                         **_model_kwargs(manifest, weights))
        blob = _load_sharding_blob(art_dir) \
            if manifest.get("sharded") else None
        if blob is not None:
            model.set_sharding(_rebuild_mesh(blob["axes"]),
                               weight_specs=blob["weight_specs"],
                               in_spec=blob["in_spec"],
                               out_spec=blob["out_spec"])
        else:
            model.set_mesh(_single_device_mesh())

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline
    from mmlspark_tpu.serving.server import HTTPSource, ServingEngine
    kwargs = {} if manifest["kind"] == "pipeline" \
        else {"field": manifest["serve"]["field"]}
    stage = json_scoring_pipeline(model, **kwargs)
    # warm through the SERVING path (the production replica discipline:
    # the swap protocol's warmup hook). AOT mode pays signature-hits;
    # trace mode pays the per-bucket trace+compile this module retires.
    with open(os.path.join(art_dir, _EXAMPLE), "rb") as f:
        example = pickle.load(f)
    warmup = getattr(stage, "warmup", None)
    if callable(warmup):
        warmup(DataTable(dict(example))
               if manifest["kind"] == "pipeline" else example)
    t_ready = time.perf_counter()
    source = HTTPSource(port=port)
    engine = ServingEngine(source, stage, batch_size=64,
                           version=manifest.get("version", "v0"),
                           tracing=False).start()
    with open(os.path.join(art_dir, _EXAMPLE_REQUEST), "rb") as f:
        body = f.read()
    misses_before = int(model.jit_cache_misses)
    req = urllib.request.Request(source.address, data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        code = resp.status
        resp.read()
    t_200 = time.perf_counter()
    request_traces = int(model.jit_cache_misses) - misses_before
    engine.stop()
    return {
        "mode": mode,
        "ok": code == 200,
        "cold_start_to_first_200_ms": round((t_200 - t0) * 1e3, 1),
        "model_ready_ms": round((t_ready - t0) * 1e3, 1),
        "first_request_ms": round((t_200 - t_ready) * 1e3, 1),
        "jit_traces_total": int(model.jit_cache_misses),
        "jit_traces_at_request_time": request_traces,
        "precision": manifest.get("precision", "f32"),
        "format": manifest.get("format"),
        "backend": _backend(),
    }


def main(argv: Optional[List[str]] = None) -> int:
    # the clock starts HERE — before jax/flax/model imports, which are
    # all lazy in this module precisely so a fresh replica's import
    # cost lands inside the measured window for BOTH modes
    t0 = time.perf_counter()
    import argparse
    ap = argparse.ArgumentParser(
        description="AOT serving artifact cold-start runner")
    ap.add_argument("artifact", help="artifact directory (export_model)")
    ap.add_argument("--mode", choices=["aot", "trace"], default="aot")
    ap.add_argument("--port", type=int, default=18980)
    args = ap.parse_args(argv)
    out = _coldstart(args.artifact, args.mode, args.port, t0)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
