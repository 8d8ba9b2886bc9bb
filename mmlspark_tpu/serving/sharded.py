"""Mesh-sharded serving: pjit-compiled inference over a named mesh.

Serving so far ran replicated single-device models — one replica = one
chip, the fleet scales out. This module makes the serving path
MESH-NATIVE (the ROADMAP sharded-serving item): serving programs compile
once per shape bucket as ``jit`` with **explicit**
``in_shardings``/``out_shardings`` over a ``parallel/mesh.py`` mesh and
donated input buffers — the standard sharded-inference shape of GSPMD
(Xu et al., 2021) and *Efficiently Scaling Transformer Inference*
(Pope et al., 2022). Three placements, one per serving family:

- **Pipeline families** (``data_shard_pipeline``): fused
  Featurize→model programs (core/fusion.py) shard the BATCH dim over
  the ``data`` axis; per-stage consts replicate (or shard per an
  explicit per-op spec); ``DeviceTable`` ships every column/feed/const
  straight into its declared placement. Bit-identical to the
  single-device program — batch-dim sharding never changes a row's
  math.
- **Tensor parallelism** (``tensor_shard_model``): a ``TPUModel`` whose
  weight matrices shard across the ``model`` axis
  (``auto_weight_specs``: largest divisible dim, small leaves stay
  replicated) with inputs/outputs replicated — XLA inserts the
  collectives. This is how a model whose weights exceed one device's
  memory serves from the mesh: per-device resident bytes stay below
  the total weight bytes (``device_residency`` proves it).
- **Sequence parallelism** (``seq_shard_lm``): the Transformer-LM zoo
  model scores LONG CONTEXTS with its sequence dim sharded over the
  ``seq`` axis, reusing the existing ring/Ulysses attention
  (parallel/ring_attention.py) inside ``shard_map`` — weights
  replicated, the attention collective is the only cross-shard
  traffic.

Every sharded program declares its shardings explicitly — never
inferred from operand placement (tools/check_fusion_kernels.py
``check_sharded_serving`` audits the jit call sites). On this CPU
container the mesh is simulated with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (tests/conftest
forces it; ``serving/aot.py``'s runner re-forces it in fresh processes
from the artifact manifest).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.core.fusion import (
    FusedPipelineModel, SegmentSharding, fuse, register_kernel,
)
from mmlspark_tpu.parallel import mesh as mesh_lib

DATA_AXIS = mesh_lib.DATA_AXIS
MODEL_AXIS = mesh_lib.MODEL_AXIS
SEQ_AXIS = mesh_lib.SEQ_AXIS

# weight leaves smaller than this stay replicated under
# auto_weight_specs: sharding a bias vector buys nothing and costs a
# collective; the big matrices (embeddings, Dense kernels) are where
# per-device memory goes
DEFAULT_MIN_SHARD_BYTES = 1 << 15


def serving_mesh(axes: Optional[Dict[str, int]] = None) -> Mesh:
    """The serving mesh: all devices on the ``data`` axis by default
    (``axes`` overrides, e.g. ``{"model": 8}`` for tensor parallelism
    or ``{"seq": 8}`` for long-context scoring)."""
    return mesh_lib.make_mesh(axes or {DATA_AXIS: -1})


# ---------------------------------------------------------------------------
# placement rules
# ---------------------------------------------------------------------------


def auto_weight_specs(weights: Any, mesh: Mesh, axis: str = MODEL_AXIS,
                      min_shard_bytes: int = DEFAULT_MIN_SHARD_BYTES,
                      ) -> Any:
    """Per-leaf ``PartitionSpec`` tree: shard each weight leaf's
    LARGEST dim that divides the axis size (ties break toward the
    first), replicate leaves smaller than ``min_shard_bytes`` or with
    no divisible dim — the naive-sharding rule of SNIPPETS [3], which
    is exactly what fitting an oversized model onto N chips needs."""
    n = int(mesh.shape[axis])

    def spec_for(leaf) -> P:
        arr = np.asarray(leaf) if not hasattr(leaf, "shape") else leaf
        shape = tuple(getattr(arr, "shape", ()))
        nbytes = int(getattr(arr, "nbytes",
                             np.asarray(arr).nbytes if shape else 0))
        if not shape or nbytes < min_shard_bytes:
            return P()
        divisible = [(d, i) for i, d in enumerate(shape) if d % n == 0]
        if not divisible:
            return P()
        _, dim = max(divisible, key=lambda t: (t[0], -t[1]))
        parts: list = [None] * len(shape)
        parts[dim] = axis
        return P(*parts)

    return jax.tree_util.tree_map(spec_for, weights)


def device_residency(obj: Any) -> Dict[str, Any]:
    """Per-device resident bytes of a served model's device state.

    ``obj`` is a ``TPUModel`` (weights ship if they haven't yet), a
    ``FusedPipelineModel`` (DeviceTable consts + cached columns), or a
    plain pytree of jax arrays. Returns ``{"per_device_bytes",
    "max_device_bytes", "total_bytes", "devices"}`` — the
    too-big-for-one-device proof is ``max_device_bytes <
    total_logical_bytes`` (and the eviction-cost signal the zoo sums
    is ``total_bytes`` across the mesh)."""
    per: Dict[str, int] = {}

    def add(leaf) -> None:
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            return
        # all-or-nothing per leaf (the fusion._shard_bytes contract):
        # a donated/deleted buffer must not leave a partial per-device
        # count behind
        try:
            counts = [(str(s.device), int(s.data.nbytes))
                      for s in shards]
        except Exception:  # noqa: BLE001 — donated/deleted buffer
            return
        for key, nbytes in counts:
            per[key] = per.get(key, 0) + nbytes

    if hasattr(obj, "_weights_on_device"):          # TPUModel
        tree = obj._weights_on_device()
        for leaf in jax.tree_util.tree_leaves(tree):
            add(leaf)
    elif isinstance(obj, FusedPipelineModel):
        with obj._plan_lock:
            plans = list(obj._plans.values())
        for plan in plans:
            dt = plan.device_table
            with dt._lock:
                trees = [t for _, t in dt._consts.values()]
                cols = [a for p_ in dt._tables.values()
                        for a in p_.values()]
            for tree in trees:
                for leaf in jax.tree_util.tree_leaves(tree):
                    add(leaf)
            for arr in cols:
                add(arr)
    else:                                           # pytree of arrays
        for leaf in jax.tree_util.tree_leaves(obj):
            add(leaf)
    total = sum(per.values())
    return {
        "per_device_bytes": per,
        "max_device_bytes": max(per.values()) if per else 0,
        "total_bytes": total,
        "devices": len(per),
    }


# ---------------------------------------------------------------------------
# the three serving placements
# ---------------------------------------------------------------------------


def data_shard_pipeline(pipeline: Any, mesh: Optional[Mesh] = None,
                        data_axis: str = DATA_AXIS,
                        const_specs: Optional[Dict[str, Any]] = None,
                        batch_size: int = 256) -> FusedPipelineModel:
    """Compile a fitted pipeline for mesh-sharded fused serving: every
    shape bucket's program jits with explicit batch-dim
    ``in_shardings``/``out_shardings`` over ``data_axis`` and donated
    inputs; ``DeviceTable`` consts replicate (``const_specs`` shards
    named ops' tables). Drop-in for ``fuse()`` — same serving
    discipline (buckets, warmup, jit_cache_misses), bit-identical
    outputs."""
    mesh = mesh if mesh is not None else serving_mesh()
    fused = pipeline if isinstance(pipeline, FusedPipelineModel) \
        else fuse(pipeline, batch_size=batch_size)
    return fused.shard(mesh, data_axis=data_axis,
                       const_specs=const_specs)


def tensor_shard_model(model: Any, mesh: Optional[Mesh] = None,
                       axis: str = MODEL_AXIS,
                       min_shard_bytes: int = DEFAULT_MIN_SHARD_BYTES,
                       weight_specs: Any = None) -> Any:
    """Tensor-parallel serving for a ``TPUModel`` too big for one
    device: weights shard across ``axis`` (``auto_weight_specs`` unless
    an explicit spec tree is given), inputs/outputs replicate, and the
    forward jits with those shardings declared — XLA inserts the
    collectives (GSPMD). Returns the model, configured in place."""
    mesh = mesh if mesh is not None else serving_mesh({axis: -1})
    if weight_specs is None:
        weight_specs = auto_weight_specs(model.get("weights"), mesh,
                                         axis=axis,
                                         min_shard_bytes=min_shard_bytes)
    return model.set_sharding(mesh, weight_specs=weight_specs,
                              in_spec=P(), out_spec=P())


class _SeqShardedApply:
    """Picklable seq-parallel LM forward: ``shard_map`` over the
    ``seq`` axis around a seq-axis-aware ``networks.Transformer``
    (ring/Ulysses attention inside — parallel/ring_attention.py).
    Weights replicate at the shard_map boundary; the attention
    collective is the only cross-shard traffic (the
    ``seq_parallel_apply`` contract, packaged as a TPUModel modelFn).

    The mesh itself is NOT pickled (Device handles are process-local):
    ``__getstate__`` keeps only the axis sizes and the fn rebuilds the
    mesh from the loading process's devices on first call — the AOT
    fallback path in a fresh replica just works."""

    int_input = True   # consumes token ids, not float features

    def __init__(self, module, mesh: Mesh, axis: str = SEQ_AXIS):
        self.module = module
        self.axis = str(axis)
        self.mesh_axes = {str(k): int(v) for k, v in mesh.shape.items()}
        self._mesh = mesh
        self._fn = None

    def __getstate__(self):
        return {"module": self.module, "axis": self.axis,
                "mesh_axes": self.mesh_axes}

    def __setstate__(self, state):
        self.module = state["module"]
        self.axis = state["axis"]
        self.mesh_axes = state["mesh_axes"]
        self._mesh = None
        self._fn = None

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = mesh_lib.make_mesh(dict(self.mesh_axes))
        return self._mesh

    def _build(self):
        if self._fn is not None:
            return self._fn
        from jax import shard_map
        module, axis = self.module, self.axis
        out_spec = (P(None, axis) if module.num_classes == 0 else P())

        def apply(vars_, toks):
            return module.apply(vars_, toks)

        self._fn = shard_map(apply, mesh=self.mesh,
                             in_specs=(P(), P(None, axis)),
                             out_specs=out_spec, check_vma=False)
        return self._fn

    def __call__(self, weights, inputs: Dict[str, jnp.ndarray]):
        toks = list(inputs.values())[0]
        variables = weights if (isinstance(weights, dict)
                                and "params" in weights) \
            else {"params": weights}
        return self._build()(variables, toks)


register_kernel(_SeqShardedApply.__call__, "sharded.seq_lm_apply")


def seq_shard_lm(module, variables: Any, mesh: Optional[Mesh] = None,
                 seq_axis: str = SEQ_AXIS, **model_kw) -> Any:
    """Serve a ``networks.Transformer`` with its SEQUENCE dim sharded
    over the mesh — long-context scoring through the existing
    ring/Ulysses attention. ``module`` must carry ``seq_axis=seq_axis``
    (build it so); token ids arrive ``[B, T]`` with ``T`` divisible by
    the axis size. Returns a ``TPUModel`` whose jitted forward declares
    tokens ``P(None, seq_axis)`` in/out (LM head) or replicated out
    (classifier head) — the serving discipline (buckets, warmup,
    donation, jit_cache_misses) is unchanged."""
    from mmlspark_tpu.models.tpu_model import TPUModel
    mesh = mesh if mesh is not None else serving_mesh({seq_axis: -1})
    if getattr(module, "seq_axis", None) != seq_axis:
        raise ValueError(
            f"module.seq_axis is {getattr(module, 'seq_axis', None)!r}; "
            f"build the Transformer with seq_axis={seq_axis!r} so its "
            f"attention runs the ring/Ulysses collective")
    fn = _SeqShardedApply(module, mesh, axis=seq_axis)
    if not (isinstance(variables, dict) and "params" in variables):
        variables = {"params": variables}
    model = TPUModel(modelFn=fn, weights=dict(variables), **model_kw)
    out_spec = (P(None, seq_axis) if module.num_classes == 0 else P())
    return model.set_sharding(mesh, weight_specs=P(),
                              in_spec=P(None, seq_axis),
                              out_spec=out_spec)


def assert_serves_from_mesh(model: Any,
                            ) -> Tuple[int, int]:
    """The too-big-for-one-device assertion, packaged: returns
    ``(max_device_bytes, total_logical_bytes)`` and raises when any
    single device holds the full weight set (i.e. the model is NOT
    actually sharded)."""
    res = device_residency(model)
    total_logical = int(sum(
        int(np.asarray(a).nbytes) if not hasattr(a, "nbytes")
        else int(a.nbytes)
        for a in jax.tree_util.tree_leaves(
            model.get("weights") if hasattr(model, "get") else model)))
    if res["max_device_bytes"] >= total_logical:
        raise AssertionError(
            f"model is not sharded: one device holds "
            f"{res['max_device_bytes']} bytes >= the full "
            f"{total_logical}-byte weight set")
    return res["max_device_bytes"], total_logical
