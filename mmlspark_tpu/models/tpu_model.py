"""TPUModel — batched DNN inference over tables.

TPU-native analog of the reference's CNTKModel
(ref: src/cntk-model/src/main/scala/CNTKModel.scala:147-514):
where the reference broadcasts a serialized CNTK graph to executors,
clones it per partition with shared weights, and feeds minibatched rows
through JNI (``CNTKModelUtils.applyModel``/``applyCNTKFunction``
:30-140), we hold a JAX apply function + weights pytree, jit it once per
(batch-shape, dtype), shard the batch over the mesh's data axis, and let
XLA run the whole minibatch on the MXU. ``feedDict``/``fetchDict``
multi-input/output maps follow CNTKModel.scala:206-225; input coercion
(float/double/vector) follows :419-462.

The weights are device-resident and replicated across the mesh — the
analog of the reference's broadcast + ``ParameterCloningMethod.Share``
(:83) without any copy per partition. The ``weights`` Param keeps what
the caller gave; the device copy holds each leaf in the dtype the model
function reads it in (``TPUModel._weights_on_device``).
"""

from __future__ import annotations

from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
)

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.core.metrics import LatencyHistogram, histogram_set
from mmlspark_tpu.core.params import (
    DictParam, EnumParam, HasInputCol, HasOutputCol, IntParam, PyTreeParam,
    StringParam, UDFParam,
)
from mmlspark_tpu.core.schema import Field, ImageSchema, Schema, TENSOR, VECTOR
from mmlspark_tpu.core.stage import Model
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.core.trace import phase
from mmlspark_tpu.parallel import mesh as mesh_lib

# smallest serving shape bucket: ragged micro-batches pad UP to the next
# power of two from here, so the compiled-executable set stays
# log2(batchSize)-sized (see TPUModel.bucket_sizes)
MIN_BUCKET = 8
# model outputs under this prefix are per-row numbers for the model's
# histograms, not columns (see TPUModel.transform's flush)
STAT_PREFIX = "stat."


class _Reading(NamedTuple):
    """One abstract trace of a model function at one bucket."""
    signature: Dict[str, Tuple]     # the inputs it was traced at
    jaxpr: Any                      # ClosedJaxpr over (weights, inputs)
    out_tree: Any                   # structure of the function's output
    dtypes: List[Any]               # the dtype it reads each leaf in


def _read_model_fn(model_fn: Callable, weights: Any,
                   inputs: Dict[str, Any]) -> _Reading:
    """Trace ``model_fn`` once at ``inputs`` and read from its jaxpr the
    dtype it reads each leaf of ``weights`` in, in flattening order.

    A floating leaf whose EVERY use is a ``convert_element_type`` to one
    narrower floating dtype is read in that dtype: the cast is a pure
    function of the leaf, and left in the program XLA fuses it into the
    product that reads it, where the product's tile loop repeats it (a
    float32 kernel of GPT-2-XL was streamed from HBM 32 times a step).
    Any other use — raw, sliced first, passed whole into a sub-jaxpr
    (``pjit``, ``scan``, a Pallas call), returned, raised to a wider
    dtype — and every leaf that is not floating (int8 kernels) reads
    the leaf as held."""
    n = len(jax.tree_util.tree_leaves(weights))
    closed, out_shape = jax.make_jaxpr(model_fn, return_shape=True)(
        weights, inputs)
    jaxpr = closed.jaxpr
    held = jaxpr.invars[:n]
    # per leaf, the target dtype of each convert that reads it; None
    # stands for any other use
    uses: Dict[Any, set] = {v: set() for v in held}
    for eqn in jaxpr.eqns:
        to = None
        if eqn.primitive.name == "convert_element_type" \
                and not eqn.params.get("weak_type") \
                and eqn.params.get("sharding") is None:
            to = eqn.params["new_dtype"]
        for v in eqn.invars:
            if isinstance(v, jax_core.Var) and v in uses:
                uses[v].add(to)
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var) and v in uses:
            uses[v].add(None)
    dtypes = []
    for v in held:
        dt = v.aval.dtype
        if len(uses[v]) == 1 and jnp.issubdtype(dt, jnp.floating):
            to, = uses[v]
            if to is not None and jnp.issubdtype(to, jnp.floating) \
                    and jnp.dtype(to).itemsize < dt.itemsize:
                dt = jnp.dtype(to)
        dtypes.append(dt)
    return _Reading(_signature(inputs), closed,
                    jax.tree_util.tree_structure(out_shape), dtypes)


def _signature(inputs: Dict[str, Any]) -> Dict[str, Tuple]:
    return {k: (tuple(v.shape), jnp.dtype(v.dtype))
            for k, v in inputs.items()}


def _column_to_array(col, field: Field, dtype) -> np.ndarray:
    """Coerce a table column into a dense batch array
    (ref: CNTKModel.scala:419-462 coerceDFAndFeedDict)."""
    if field is not None and ImageSchema.is_image(field):
        return np.stack([np.asarray(r[ImageSchema.DATA]) for r in col]
                        ).astype(dtype)
    if isinstance(col, np.ndarray):
        return np.asarray(col, dtype=dtype)
    first = next((x for x in col if x is not None), None)
    if isinstance(first, np.ndarray):
        return np.stack([np.asarray(x) for x in col]).astype(dtype)
    return np.asarray(col, dtype=dtype)


class TPUModel(Model, HasInputCol, HasOutputCol):
    """Run a jitted forward function over a table, minibatched + sharded.

    The model is ``model_fn(weights, inputs: dict[str, Array]) ->
    dict[str, Array] | Array``. Use ``from_flax`` / ``from_fn`` to build.

    What is held where: the ``weights`` Param is the tree as given
    (``save``, ``quantize`` and ``device_op`` read it). The
    device copy the forward runs on holds each leaf in the dtype
    ``model_fn`` reads it in: a floating leaf the function only ever
    converts to one narrower floating dtype (a float32 kernel under
    ``nn.Dense(dtype=bfloat16)``) is converted once as it is placed,
    every other leaf is placed as held (``_read_model_fn``). The outputs
    are the same numbers; the step stops reading and converting the
    wider copy on every call. ``metrics()`` counts the converted leaves
    (``weights_cast_leaves``, ``weights_cast_bytes``).
    """

    modelFn = UDFParam("callable (weights, inputs dict) -> outputs", default=None)
    weights = PyTreeParam("model weights pytree", default=None)
    feedDict = DictParam(
        "map model input name -> table column "
        "(ref: CNTKModel feedDict :206)", default=None)
    fetchDict = DictParam(
        "map output column -> model output name "
        "(ref: CNTKModel fetchDict :217)", default=None)
    batchSize = IntParam("minibatch size", default=64)
    # float64 deliberately absent: JAX canonicalizes f64->f32 unless the
    # global jax_enable_x64 flag is on, which we don't silently toggle
    computeDtype = EnumParam(["float32", "bfloat16"],
                             "on-device compute dtype", default="float32")
    # serving precision label: 'int8' models carry per-channel-quantized
    # Dense weights + calibrated activation scales in the weights tree
    # (core/quantize.py) and run int8xint8->i32 matmuls with f32 dequant
    # epilogues. Set by quantize(), surfaced on /healthz + /metrics.
    precision = EnumParam(["f32", "int8"],
                          "inference precision (set by quantize())",
                          default="f32")

    def _post_init(self):
        self._mesh: Optional[Mesh] = None
        # explicit mesh sharding (set_sharding / serving/sharded.py):
        # when set, the forward jits with DECLARED in_shardings/
        # out_shardings (weights per their spec tree — sharded weights
        # are how a model too big for one device serves from the mesh —
        # inputs/outputs per in_spec/out_spec) instead of the
        # replicate-weights + shard-batch default
        self._sharding: Optional[Dict[str, Any]] = None
        # True on models rebuilt from an AOT artifact (serving/aot.py);
        # exported as the serving_model_info 'aot' label
        self.aot = False
        self._jitted: Dict[Tuple, Callable] = {}
        self._device_weights = None
        # the trace of modelFn the weights were placed by: the dtype
        # it reads each leaf in, decided once per (modelFn, weights) at
        # the first bucket and held to by every later one
        self._placement: Optional[_Reading] = None
        # leaves placed narrower than held, and the bytes that saves
        self._cast = (0, 0)
        # lazy init is shared mutable state; concurrent first calls
        # (multi-worker serving engines) must not race it — a race would
        # device_put N transient copies of the full weight tree
        import threading
        self._init_lock = threading.Lock()
        # one increment per jit TRACE of the forward (== one XLA compile
        # per distinct bucket shape/dtype): the recompile-guard signal
        # for steady-state serving. Lock-guarded — concurrent worker
        # threads can first-trace two buckets at once, and a bare +=
        # is a read-modify-write that could drop a count.
        self.jit_cache_misses = 0
        self._miss_lock = threading.Lock()
        # serving-path breakdown: host batch assembly + padding vs the
        # device dispatch->readback round trip, and of that the blocked
        # read of the outputs alone (exported through ServingEngine
        # /healthz via the duck-typed .metrics hook)
        self._hists = histogram_set("pad_ms", "device_ms", "readback_ms")
        # rows the micro-batches held and rows of the buckets they were
        # padded to, summed: their ratio is how full the buckets ran
        self._rows = [0, 0]
        self._rows_lock = threading.Lock()

    def _on_param_change(self, name: str) -> None:
        if name in ("weights", "modelFn"):
            self._unplace()
        if name == "modelFn":
            self._jitted = {}

    def _unplace(self) -> None:
        """Forget the device copy of the weights and the decision it
        was placed by; the next call places them again."""
        self._device_weights = None
        self._placement = None
        self._cast = (0, 0)

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_fn(fn: Callable, weights: Any, **kw) -> "TPUModel":
        return TPUModel(modelFn=fn, weights=weights, **kw)

    @staticmethod
    def from_flax(module, variables: Any, method=None, **kw) -> "TPUModel":
        """Wrap a flax module; inputs dict values are passed positionally
        in feedDict order (single input the common case). ``variables`` is
        the full init() result — every collection (params, batch_stats, …)
        is kept so BatchNorm-style models work at inference."""
        fn = _FlaxApply(module, method)
        if not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": variables}
        model = TPUModel(modelFn=fn, weights=dict(variables), **kw)
        # a module's per-row counters (``row_stats``) are histograms of
        # the model from the start, so that an exporter sees them all
        for name in getattr(module, "row_stats", ()):
            model._hists[name] = LatencyHistogram(unit="count")
        return model

    # -- mesh / jit management ----------------------------------------------

    def set_mesh(self, mesh: Optional[Mesh]) -> "TPUModel":
        self._mesh = mesh
        self._jitted = {}
        self._unplace()
        return self

    def set_sharding(self, mesh: Mesh, weight_specs: Any = None,
                     in_spec: Optional[P] = None,
                     out_spec: Optional[P] = None) -> "TPUModel":
        """Mesh-shard this model's serving program (the pjit pattern:
        jit with explicit ``in_shardings``/``out_shardings`` over a
        named mesh; GSPMD, Xu et al. 2021 / Pope et al. 2022).

        - ``weight_specs``: a ``PartitionSpec``, a pytree of specs
          matching the weights, or a callable ``(path, leaf) -> spec``
          (see ``serving.sharded.auto_weight_specs``). Default:
          replicated. Sharded weight leaves are how a model whose
          weights exceed one device's memory serves from the mesh —
          per-device resident bytes stay below the total.
        - ``in_spec``: placement of every model input (default:
          batch-dim over ``'data'`` when the mesh has that axis, else
          replicated). A seq-sharded LM passes ``P(None, 'seq')``.
        - ``out_spec``: placement of every output (default =
          ``in_spec``); the readback gathers.

        Shardings here are declared, never inferred (audited by
        tools/check_fusion_kernels.py ``check_sharded_serving``)."""
        if in_spec is None:
            in_spec = P("data") if "data" in mesh.shape else P()
        if out_spec is None:
            out_spec = in_spec
        weights = self.get("weights")
        if weight_specs is None:
            weight_specs = P()
        if callable(weight_specs) and not isinstance(weight_specs, P):
            flat, treedef = jax.tree_util.tree_flatten_with_path(weights)
            specs = jax.tree_util.tree_unflatten(
                treedef, [weight_specs(jax.tree_util.keystr(path), leaf)
                          for path, leaf in flat])
        elif isinstance(weight_specs, P):
            specs = jax.tree_util.tree_map(lambda _: weight_specs,
                                           weights)
        else:
            specs = weight_specs   # a full pytree of PartitionSpecs
        w_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # batch-dim input sharding needs EVERY serving bucket (pow-2
        # from MIN_BUCKET up to batchSize) to divide the axis — refuse
        # now with the fix, not at the first small live batch that
        # buckets to 8 rows over a non-pow-2 axis
        if in_spec and in_spec[0] is not None:
            n = int(mesh.shape[in_spec[0]])
            if MIN_BUCKET % n:
                raise ValueError(
                    f"the {in_spec[0]!r} axis has {n} shards, which "
                    f"does not divide the smallest serving bucket "
                    f"({MIN_BUCKET}): small micro-batches could never "
                    f"shard")
            if int(self.get("batchSize")) % n:
                raise ValueError(
                    f"batchSize {self.get('batchSize')} does not divide "
                    f"the {in_spec[0]!r} axis ({n} shards); pick a "
                    f"multiple of {n}")
        self._sharding = {
            "mesh": mesh,
            "weight_specs": specs,
            "weight_shardings": w_shardings,
            "in": NamedSharding(mesh, in_spec),
            "in_spec": in_spec,
            "out": NamedSharding(mesh, out_spec),
            "out_spec": out_spec,
        }
        self._mesh = mesh
        self._jitted = {}
        self._unplace()
        return self

    @property
    def sharding(self) -> Optional[Dict[str, Any]]:
        return self._sharding

    def _get_mesh(self) -> Mesh:
        if self._mesh is None:
            with self._init_lock:
                if self._mesh is None:
                    self._mesh = mesh_lib.make_mesh()
        return self._mesh

    def _weights_on_device(self, inputs: Optional[Dict[str, Any]] = None):
        """The weights on the mesh, placed once (broadcast analog, ref:
        CNTKModel.scala:413 rebroadcastCNTKModel): replicated, or split
        per the declared specs of ``set_sharding``. With the first
        bucket's ``inputs`` each leaf lands in the dtype ``modelFn``
        reads it in (``_read_model_fn``), converted on the device from
        the held value; a caller with no inputs to show (a byte count
        before the first batch) gets the leaves as held, and the first
        batch converts what it must from those. Double-checked locking:
        thread-safe under multi-worker serving."""
        if self._device_weights is None or (
                self._placement is None and inputs is not None):
            self._get_mesh()
            with self._init_lock:
                reading = None
                if self._placement is None and inputs is not None:
                    reading = self._placement = self._read(inputs)
                if self._device_weights is None or reading is not None:
                    self._place(reading.dtypes if reading else None)
        return self._device_weights

    def _read(self, inputs: Dict[str, Any]) -> _Reading:
        """One abstract trace of ``modelFn`` at the bucket of ``inputs``,
        under the mesh its caller (``transform``'s dispatch) has set. A
        reading whose ``dtypes`` is None (a model with no function to
        trace, ``serving/aot.py``) places every leaf as held."""
        return _read_model_fn(self.get("modelFn"), self.get("weights"),
                              inputs)

    def _place(self, dtypes: Optional[List[Any]]) -> None:
        """Put the weights on the mesh, leaf by leaf so that at most one
        leaf is on the device twice, from the device copy where there
        is one (placed as held, before the function had been read)."""
        src = self._device_weights if self._device_weights is not None \
            else self.get("weights")
        leaves, treedef = jax.tree_util.tree_flatten(src)
        if self._sharding is not None:
            # per-leaf declared placement: sharded leaves land split
            # across the mesh (per-device resident bytes < the total)
            shardings = jax.tree_util.tree_leaves(
                self._sharding["weight_shardings"])
        else:
            shardings = [NamedSharding(self._mesh, P())] * len(leaves)
        placed, n_cast, saved = [], 0, 0
        for a, sharding, dt in zip(leaves, shardings,
                                   dtypes or [None] * len(leaves)):
            a = jax.device_put(jnp.asarray(a), sharding)
            if dt is not None and a.dtype != dt:
                n_cast += 1
                saved += a.size * (a.dtype.itemsize - dt.itemsize)
                # a cast leaf keeps its declared placement
                a = jax.device_put(a.astype(dt), sharding)
            placed.append(a)
        self._cast = (n_cast, saved)
        self._device_weights = jax.tree_util.tree_unflatten(treedef, placed)

    def _apply_read(self, placed, inputs):
        """``modelFn(placed, inputs)`` inside the jit trace of a bucket,
        by evaluating the jaxpr the bucket was read by, so that a bucket
        costs ONE Python trace of ``modelFn``: the first bucket's is the
        one its weights were placed by, a later bucket is read here. The
        leaves stand in for the held ones the jaxpr was traced over; a
        leaf placed narrower meets only its own converts there, which
        no longer convert anything. A bucket at which ``modelFn`` reads
        a leaf in another dtype than it was placed in would compute with
        other numbers than the held weights give: an error, not a
        second device copy."""
        reading = self._placement
        if reading is None or reading.signature != _signature(inputs):
            reading = self._read(inputs)
        flat = jax.tree_util.tree_leaves(placed)
        got = [a.dtype for a in flat]
        if reading.dtypes != got:
            raise ValueError(
                f"modelFn reads its weights differently at inputs "
                f"{reading.signature} than at the bucket they were "
                f"placed for: "
                f"{sum(w != g for w, g in zip(reading.dtypes, got))} "
                f"leaves differ in dtype")
        out = jax_core.jaxpr_as_fun(reading.jaxpr)(
            *flat, *jax.tree_util.tree_leaves(inputs))
        return jax.tree_util.tree_unflatten(reading.out_tree, out)

    def resident_bytes(self) -> int:
        """Device bytes the placed weights occupy, summed across PER-
        DEVICE shards over the whole mesh (a replicated tree counts
        once per device; a sharded tree counts its true split
        footprint) — the zoo's per-model eviction-cost signal. Falls
        back to the host estimate before the first ship."""
        dev = self._device_weights
        if dev is not None:
            from mmlspark_tpu.core.fusion import _shard_bytes
            return sum(_shard_bytes(leaf)
                       for leaf in jax.tree_util.tree_leaves(dev))
        host = self.get("weights")
        if host is None:
            return 0
        return int(sum(int(np.asarray(a).nbytes)
                       for a in jax.tree_util.tree_leaves(host)))

    def _feeds(self) -> Dict[str, str]:
        fd = self.get("feedDict")
        if fd:
            return dict(fd)
        return {"input": self.get_input_col()}

    def _fetches(self) -> Dict[str, str]:
        fd = self.get("fetchDict")
        if fd:
            return dict(fd)
        return {self.get_output_col(): "output"}

    def _compiled(self) -> Callable:
        """One jit wrapper per model (jax.jit handles per-shape retraces
        internally, one executable per bucket shape); invalidated when
        modelFn changes. Every trace — i.e. every compile-cache miss —
        bumps ``jit_cache_misses``, and the padded input buffers are
        DONATED on accelerator backends (a serving batch is consumed
        exactly once, so XLA may alias it for activations instead of
        holding both live in HBM)."""
        fn = self._jitted.get("run")
        if fn is None:
            with self._init_lock:
                fn = self._jitted.get("run")
                if fn is None:
                    model = self

                    def tpu_model_forward(
                            weights, inputs: Dict[str, jnp.ndarray]):
                        # trace-time side effect: runs once per distinct
                        # input signature, i.e. once per XLA compile
                        with model._miss_lock:
                            model.jit_cache_misses += 1
                        out = model._apply_read(weights, inputs)
                        if not isinstance(out, dict):
                            out = {"output": out}
                        return out

                    # CPU's donation support is backend-version dependent
                    # and only emits warnings there; donate where it pays
                    donate = (1,) if jax.default_backend() not in ("cpu",) \
                        else ()
                    # the function's name is the program's name in a
                    # profile (jit_tpu_model_forward)
                    if self._sharding is not None:
                        fn = self._jit_sharded(tpu_model_forward, donate)
                    else:
                        fn = jax.jit(tpu_model_forward,
                                     donate_argnums=donate)
                    self._jitted["run"] = fn
        return fn

    def _jit_sharded(self, run: Callable, donate: Tuple[int, ...],
                     ) -> Callable:
        """The mesh-sharded forward: jit with EXPLICIT in_shardings
        (the per-leaf weight placement + the declared input spec for
        every feed) and out_shardings, input buffers donated — never
        inferred shardings (the sharded-serving audit contract)."""
        sh = self._sharding
        return jax.jit(
            run,
            in_shardings=(sh["weight_shardings"], sh["in"]),
            out_shardings=sh["out"],
            donate_argnums=donate)

    # -- serving shape buckets ----------------------------------------------

    def bucket_sizes(self) -> List[int]:
        """The padded batch-row sizes serving traffic compiles for:
        powers of two from MIN_BUCKET up, capped by (and always
        including) batchSize. Ragged micro-batches pad UP to the nearest
        bucket, bounding the distinct compiled shapes to
        log2(batchSize)+O(1) regardless of traffic mix."""
        cap = int(self.get("batchSize"))
        sizes: List[int] = []
        b = MIN_BUCKET
        while b < cap:
            sizes.append(b)
            b *= 2
        sizes.append(cap)
        return sizes

    def warmup(self, example, sizes: Optional[List[int]] = None) -> int:
        """Pre-compile every serving bucket so no live request ever pays
        an XLA compile (bounded first-request latency — the explicit
        warmup hook of the serving hot path).

        ``example`` is a DataTable, or a dict of column -> array,
        holding at least one representative row for every feed column.
        Rows are tiled up to each bucket size and pushed through
        ``transform`` (core/warmup.py — each bucket's compile wall
        lands in the ``model_warmup_ms`` histogram on /metrics).
        Returns the number of compiles triggered (0 when everything was
        already warm)."""
        from mmlspark_tpu.core.warmup import warmup_transform
        return warmup_transform(self, example, sizes)

    def bucket_for(self, rows: int) -> int:
        """The padded bucket a ``rows``-row micro-batch compiles/runs
        at (the pow-2 padding rule of ``bucket_sizes``): serving spans
        annotate it so a trace shows which executable a batch hit."""
        cap = int(self.get("batchSize"))
        b = MIN_BUCKET
        while b < rows:
            b *= 2
        return min(b, cap)

    def histograms(self) -> Dict[str, Any]:
        """Raw pad/device histogram objects (exact buckets) for the
        Prometheus exposition — ``metrics()`` keeps returning the
        summary view."""
        return dict(self._hists)

    def metrics(self) -> Dict[str, Any]:
        """Serving instrumentation: pad/device latency summaries + the
        compile-cache miss counter (duck-typed hook consumed by
        ServingEngine's /healthz export)."""
        out: Dict[str, Any] = {k: h.summary()
                               for k, h in self._hists.items()}
        out["jit_cache_misses"] = self.jit_cache_misses
        # real rows over bucket rows is the fill: a step costs what its
        # bucket costs whatever it holds
        out["rows_real"], out["rows_bucket"] = self._rows
        # leaves placed narrower than held (the dtype modelFn reads
        # them in), and the bytes a call no longer reads for it
        out["weights_cast_leaves"], out["weights_cast_bytes"] = self._cast
        # expert layers of a wrapped module that return the experts'
        # outputs to their tokens by a gather, that run their down
        # product once a layer, its output the layer's buffer, and
        # that run a pass's gate and up products and their silu * up
        # as one kernel, and whose passes read their rows through their
        # token ids (expert_layer.py): every expert is on this chip; 0
        # for a module that has none
        module = getattr(self.get("modelFn"), "module", None)
        for name in ("moe_gather_combines", "moe_layer_down_products",
                     "moe_fused_swiglu_layers", "moe_row_fetch_layers"):
            out[name] = int(getattr(module, name, 0))
        # fetch blocks a (row, head) of a windowed and of a causal flash
        # call of the module visit at its longest row (hybrid_moe_lm):
        # the window's saving is their difference; 0 for a module that
        # has no such layer
        for name in ("flash_window_blocks", "flash_causal_blocks"):
            out[name] = int(getattr(module, name, 0))
        # attention operators of the module with an output gate,
        # attention layers that take no rotary step, and shared experts
        # an expert layer adds (hybrid_moe_lm); 0 for a module without
        for name in ("attn_gated_layers", "rope_free_layers",
                     "moe_shared_experts"):
            out[name] = int(getattr(module, name, 0))
        # Mamba-2 layers of the module, the chunks of their scan at its
        # longest row, the bytes of a row's final scan states and conv
        # tails over them, and the layers whose scan runs as one kernel
        # (hybrid_moe_lm); 0 for a module without
        for name in ("ssm_layers", "ssm_chunks", "ssm_state_bytes",
                     "ssm_scan_kernel_layers"):
            out[name] = int(getattr(module, name, 0))
        out["precision"] = self.get("precision")
        out["aot"] = bool(self.aot)
        if self._sharding is not None:
            out["sharded"] = True
            out["mesh"] = dict(self._sharding["mesh"].shape)
            out["in_spec"] = str(self._sharding["in_spec"])
        return out

    # -- post-training quantization -----------------------------------------

    def quantize(self, calib, percentile: float = 100.0) -> "TPUModel":
        """Int8 post-training quantization (core/quantize.py): calibrate
        per-tensor activation clip ranges on the ``calib`` rows (a
        DataTable or column->array dict holding a held-out batch for
        every feed column), quantize every Dense kernel per-channel, and
        return a NEW ``TPUModel`` whose forward runs int8xint8->i32
        matmuls with f32 dequant epilogues. This model (the f32 path) is
        untouched — it stays the accuracy oracle and the swap-rollback
        target. The returned model keeps the full serving discipline
        (pow-2 buckets, ``warmup()``, ``jit_cache_misses``, donation)
        and labels itself ``precision='int8'`` on /healthz.

        Requires a flax-module model (``from_flax`` or any modelFn
        exposing ``.module``): quantization intercepts ``nn.Dense``
        calls; conv/LSTM/embedding layers stay f32 by design."""
        from mmlspark_tpu.core import quantize as QZ
        model_fn = self.get("modelFn")
        module = getattr(model_fn, "module", None)
        if module is None:
            raise ValueError(
                "quantize() needs a flax-module model (TPUModel.from_flax"
                " or a modelFn exposing .module); arbitrary callables "
                "cannot be post-training quantized")
        table = calib if isinstance(calib, DataTable) \
            else DataTable(dict(calib))
        if len(table) == 0:
            raise ValueError("quantize needs at least one calibration row")
        int_input = bool(getattr(model_fn, "int_input", False))
        host_dtype = np.int32 if int_input else np.float32
        args = []
        for _model_in, col in self._feeds().items():
            args.append(_column_to_array(table[col],
                                         table.schema.get(col),
                                         host_dtype))
        variables = self.get("weights")
        if not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": variables}
        qfn, qweights = QZ.quantize_flax(
            module, variables, args,
            method=getattr(model_fn, "method", None),
            percentile=percentile)
        # computeDtype pins to float32: the dequant epilogue contract is
        # f32, and routing int8 dequant through bf16 would stack a
        # second rounding on top of the quantization error
        return TPUModel(modelFn=qfn, weights=qweights,
                        feedDict=self.get("feedDict"),
                        fetchDict=self.get("fetchDict"),
                        batchSize=self.get("batchSize"),
                        computeDtype="float32",
                        inputCol=self.get("inputCol"),
                        outputCol=self.get("outputCol"),
                        precision="int8")

    # -- fusion hook ---------------------------------------------------------

    def reads_columns(self, schema):
        return list(self._feeds().values())

    def writes_columns(self, schema):
        return list(self._fetches().keys())

    def device_op(self, schema):
        """Fusion hook (core/fusion.py): the forward becomes one op in a
        fused pipeline program — upstream featurization flows into it
        on-device, its own minibatch/bucket machinery is bypassed (the
        fused plan owns batching). Integer-token models feed through an
        i32 Feed so ids never round-trip through float."""
        from mmlspark_tpu.core import fusion as FZ
        feeds_map = self._feeds()
        fetches = self._fetches()
        model_fn = self.get("modelFn")
        if model_fn is None:
            return None
        bf16 = self.get("computeDtype") == "bfloat16"
        int_input = bool(getattr(model_fn, "int_input", False))
        reads: List[str] = []
        op_feeds: List[Any] = []
        env_key: Dict[str, str] = {}
        for model_in, col in feeds_map.items():
            if int_input:
                name = f"{self.uid}:{col}:i32"
                op_feeds.append(FZ.Feed(
                    name, lambda t, _c=col: _column_to_array(
                        t[_c], t.schema.get(_c), np.int32)))
                env_key[model_in] = name
            else:
                reads.append(col)
                env_key[model_in] = col

        def fn(consts, env, _keys=tuple(env_key.items()),
               _fetch=tuple(fetches.items()), _bf16=bf16,
               _int=int_input):
            inputs = {}
            for model_in, key in _keys:
                x = env[key]
                if _bf16 and not _int:
                    x = x.astype(jnp.bfloat16)
                inputs[model_in] = x
            out = model_fn(consts, inputs)
            if not isinstance(out, dict):
                out = {"output": out}
            res = {}
            for out_col, model_out in _fetch:
                val = out[model_out]
                if val.dtype == jnp.bfloat16:
                    val = val.astype(jnp.float32)
                res[out_col] = val
            return res

        return FZ.DeviceOp(
            self, reads=reads, writes=list(fetches.keys()), fn=fn,
            make_consts=lambda: self.get("weights"), feeds=op_feeds,
            name=(f"{type(self).__name__}:{self.uid}:int8"
                  if self.get("precision") == "int8" else None))

    # -- transform ----------------------------------------------------------

    def transform(self, table: DataTable) -> DataTable:
        feeds = self._feeds()
        fetches = self._fetches()
        dtype = np.dtype(self.get("computeDtype")) \
            if self.get("computeDtype") != "bfloat16" else jnp.bfloat16
        batch_size = self.get("batchSize")
        mesh = self._get_mesh()

        n = len(table)
        out_cols: Dict[str, List[np.ndarray]] = {c: [] for c in fetches}

        # integer-token models (BiLSTM/Transformer) must not round-trip
        # their ids through float compute dtypes
        int_input = bool(getattr(self.get("modelFn"), "int_input", False))

        def prepare(start):
            """Host batch assembly + device_put — runs on the prefetch
            thread so transfers overlap the current batch's compute
            (the host-bound loop VERDICT flagged in :168-190). A
            partial batch is padded up to its bucket; padded rows are
            sliced off by the [:true_len] readback."""
            stop = min(start + batch_size, n)
            rows = stop - start
            bucket = self.bucket_for(rows)
            inputs = {}
            with phase("tpu_model.pad", hist=self._hists["pad_ms"],
                       rows=rows, bucket=bucket):
                for model_in, col_name in feeds.items():
                    inputs[model_in] = place(col_name, start, stop, bucket)
            return rows, bucket, inputs

        def place(col_name, start, stop, bucket):
            """One feed column's rows as a padded array on the device."""
            field = table.schema.get(col_name)
            arr = table[col_name][start:stop]
            host_dtype = np.int32 if int_input else (
                np.float32 if dtype == jnp.bfloat16 else dtype)
            arr = _column_to_array(arr, field, host_dtype)
            if bucket > stop - start:
                # edge-pad (pad_to_multiple's discipline): padded
                # rows stay VALID inputs, so models with log/1-over/
                # normalization paths can't turn them into NaNs that
                # a cross-row computation would spread to real rows
                arr, _ = mesh_lib.pad_to_multiple(arr, bucket, axis=0)
            if self._sharding is not None:
                # ship straight into the DECLARED input placement
                # (replicated for tensor parallelism, seq-sharded
                # for the ring-attention LM, batch-sharded for DP)
                # so the sharded executable never reshuffles inputs
                sharded = jax.device_put(arr, self._sharding["in"])
            else:
                sharded, _ = mesh_lib.shard_batch(mesh, arr)
            if dtype == jnp.bfloat16 and not int_input:
                sharded = sharded.astype(jnp.bfloat16)
            return sharded

        def flush(item):
            true_len, outputs, t_dispatch = item
            device = [outputs[m].astype(jnp.float32)
                      if outputs[m].dtype == jnp.bfloat16 else outputs[m]
                      for m in fetches.values()]
            stat_out = {k[len(STAT_PREFIX):]: v for k, v in outputs.items()
                        if k.startswith(STAT_PREFIX)}
            # every copy is asked for now, while the step still runs, so
            # that the runtime moves each result out as the program ends
            # and not one after another as the reads below ask (a
            # megabyte of logits and three counters read one by one
            # ended 4.3-5.4 ms after the step: PERF.md, PR 34)
            for d in (*device, *stat_out.values()):
                if hasattr(d, "copy_to_host_async"):
                    d.copy_to_host_async()
            # the blocked read alone: it ends when the device does
            with phase("tpu_model.readback",
                       hist=self._hists["readback_ms"],
                       rows=true_len) as read:
                host = [np.asarray(d) for d in device]
                stats = {k: np.asarray(v)[:true_len]
                         for k, v in stat_out.items()}
            for out_col, val in zip(fetches, host):
                out_cols[out_col].append(val[:true_len])
            for name, per_row in stats.items():
                for value in per_row:
                    self._hists[name].observe(value)
            # dispatch -> readback-complete: the device round trip as
            # the serving path experiences it (async dispatch means the
            # compiled call alone measures nothing)
            self._hists["device_ms"].observe(
                (read.end - t_dispatch) * 1e3)

        def dispatch(rows, bucket, inputs):
            # traced under the mesh: kernels that XLA cannot partition
            # (ring_attention.flash_per_shard) read it
            with phase("tpu_model.dispatch", rows=rows,
                       bucket=bucket) as sent, jax.set_mesh(mesh):
                outputs = self._compiled()(
                    self._weights_on_device(inputs), inputs)
            with self._rows_lock:
                self._rows[0] += rows
                self._rows[1] += bucket
            for model_out in fetches.values():
                if model_out not in outputs:
                    raise KeyError(
                        f"model output {model_out!r} not in outputs "
                        f"{list(outputs)}")
            return outputs, sent.start

        if 0 < n <= batch_size:
            # serving fast path: one micro-batch — prepare, dispatch,
            # read back inline. The prefetcher buys nothing here and
            # costs a thread spawn + queue handshake per request batch
            # on accelerator backends.
            rows, bucket, inputs = prepare(0)
            flush((rows, *dispatch(rows, bucket, inputs)))
        else:
            from mmlspark_tpu.utils.prefetch import make_prefetcher
            feed = make_prefetcher(iter(range(0, n, batch_size)), prepare,
                                   depth=2)
            pending: List[Tuple[int, Dict[str, jnp.ndarray], float]] = []
            try:
                for rows, bucket, inputs in feed:
                    pending.append((rows,
                                    *dispatch(rows, bucket, inputs)))
                    if len(pending) > 1:
                        # delayed-by-one readback: batch k's D2H happens
                        # while batch k+1 runs on device
                        flush(pending.pop(0))
            finally:
                feed.close()
            for item in pending:
                flush(item)

        result = table
        for out_col, parts in out_cols.items():
            merged = np.concatenate(parts, axis=0) if parts else np.empty((0,))
            tag = VECTOR if merged.ndim == 2 else TENSOR if merged.ndim > 2 \
                else Field(out_col, "f32").tag
            result = result.with_column(out_col, merged, Field(out_col, tag))
        return result

    def transform_schema(self, schema: Schema) -> Schema:
        for col_name in self._feeds().values():
            schema.require(col_name)
        out = schema
        for out_col in self._fetches():
            out = out.add_or_replace(Field(out_col, VECTOR))
        return out


class _FlaxApply:
    """Picklable flax apply wrapper (module defs pickle by value of their
    config, weights travel separately as a PyTreeParam)."""

    def __init__(self, module, method=None):
        self.module = module
        self.method = method
        self.int_input = bool(getattr(module, "int_input", False))

    def __call__(self, weights, inputs: Dict[str, jnp.ndarray]):
        args = list(inputs.values())
        variables = weights if (isinstance(weights, dict)
                                and "params" in weights) else {"params": weights}
        if self.method is not None:
            return self.module.apply(variables, *args, method=self.method)
        names = getattr(self.module, "row_stats", ())
        arrays = getattr(self.module, "row_outputs", ())
        if not names and not arrays:
            return self.module.apply(variables, *args)
        # a module that sows per-row numbers into "stats" hands them
        # out beside its output, one (rows,) array a name; its
        # ``row_outputs`` are sown there too and go out under their own
        # names, as outputs that ``fetchDict`` can name
        variables = {k: v for k, v in variables.items() if k != "stats"}
        out, sown = self.module.apply(variables, *args, mutable=["stats"])
        return {"output": out,
                **{STAT_PREFIX + n: sown["stats"][n][-1] for n in names},
                **{n: sown["stats"][n][-1] for n in arrays
                   if n in sown["stats"]}}
