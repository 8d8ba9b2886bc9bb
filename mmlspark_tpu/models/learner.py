"""TPULearner — minibatch SGD training of zoo networks as an Estimator.

TPU-native replacement for the reference's cntk-train component
(ref: src/cntk-train/src/main/scala/CNTKLearner.scala:88-176): where the
reference writes the dataset to CNTKTextFormat, emits BrainScript configs,
and shells out to ``mpirun cntk`` over ssh with scp'd data and hostfiles
(ref: CommandBuilders.scala:108-267), we build a flax network from a
declarative spec, jit one train step over a named device mesh, and stream
host-sharded minibatches through it:

- **DP**: batch sharded over the ``data`` axis; XLA inserts the gradient
  all-reduce (psum) over ICI — the analog of CNTK's MPI 1-bit SGD ring.
- **FSDP**: optionally shard each param's largest divisible dim over the
  mesh so optimizer state and weights scale past one chip's HBM.
- **bf16 compute / f32 params**: MXU-friendly mixed precision.
- **Masked final batch**: shapes stay static (no recompiles); padded rows
  carry zero loss weight.
- **Checkpoint/resume**: train state snapshots every N steps
  (ref analog: model persistence via ConstructorWritable + LightGBM
  modelString warm-start, SURVEY.md §5).

``fit`` returns a :class:`TPUModel` ready for batched inference — the
same contract as CNTKLearner returning a CNTKModel (:172-175).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import optax

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.params import (
    BoolParam, DictParam, EnumParam, FloatParam, HasFeaturesCol, HasLabelCol,
    IntParam, StringParam, UDFParam,
)
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.core.stage import Estimator
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.core import serialize as ser
from mmlspark_tpu.models.networks import build_network
from mmlspark_tpu.models.tpu_model import TPUModel
from mmlspark_tpu.parallel import mesh as mesh_lib

logger = get_logger("learner")


# ---------------------------------------------------------------------------
# optimizers / schedules
# ---------------------------------------------------------------------------


def make_optimizer(name: str, lr: float, *, momentum: float = 0.9,
                   weight_decay: float = 0.0, schedule: str = "constant",
                   warmup_steps: int = 0, total_steps: int = 1000
                   ) -> optax.GradientTransformation:
    if schedule == "cosine":
        w = max(warmup_steps, 1)
        sched = optax.warmup_cosine_decay_schedule(
            0.0, lr, w, max(total_steps, w + 1))
    elif schedule == "constant":
        if warmup_steps > 0:
            sched = optax.linear_schedule(0.0, lr, warmup_steps)
        else:
            sched = lr
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    if name == "sgd":
        return optax.sgd(sched)
    if name == "momentum":
        return optax.sgd(sched, momentum=momentum, nesterov=True)
    if name == "adam":
        return optax.adam(sched)
    if name == "adamw":
        return optax.adamw(sched, weight_decay=weight_decay)
    raise ValueError(f"unknown optimizer {name!r}")


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def fsdp_sharding_rule(mesh: Mesh, axis: str = mesh_lib.FSDP_AXIS
                       ) -> Callable[[jnp.ndarray], NamedSharding]:
    """Shard each leaf's largest dim divisible by the axis size; replicate
    otherwise (simple ZeRO-3-style rule)."""
    size = mesh.shape[axis]

    def rule(leaf) -> NamedSharding:
        shape = getattr(leaf, "shape", ())
        if not shape or size == 1:
            return NamedSharding(mesh, P())
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for d in dims:
            if shape[d] % size == 0 and shape[d] >= size:
                spec = [None] * len(shape)
                spec[d] = axis
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return rule


# ---------------------------------------------------------------------------
# feature extraction from table columns
# ---------------------------------------------------------------------------


def table_to_xy(table: DataTable, features_col: str, label_col: str,
                input_shape: Optional[List[int]] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    field = table.schema.get(features_col)
    col = table[features_col]
    if field is not None and ImageSchema.is_image(field):
        x = np.stack([np.asarray(r[ImageSchema.DATA]) for r in col]
                     ).astype(np.float32) / 255.0
    elif isinstance(col, np.ndarray):
        x = np.asarray(col, dtype=np.float32)
    else:
        x = np.stack([np.asarray(v) for v in col]).astype(np.float32)
    if input_shape:
        x = x.reshape((x.shape[0],) + tuple(input_shape))
    y = np.asarray(table[label_col])
    return x, y


class TPULearner(Estimator, HasFeaturesCol, HasLabelCol):
    """Train a zoo network on a table; returns a TPUModel."""

    networkSpec = DictParam(
        "declarative network spec, e.g. {'type':'resnet',...} "
        "(BrainScript analog, ref: BrainscriptBuilder.scala:16)", default=None)
    moduleFactory = UDFParam(
        "callable () -> flax Module (alternative to networkSpec)", default=None)
    loss = EnumParam(["cross_entropy", "mse", "token_cross_entropy"],
                     "training loss", default="cross_entropy")
    optimizer = EnumParam(["sgd", "momentum", "adam", "adamw"],
                          "optimizer", default="momentum")
    learningRate = FloatParam("peak learning rate", default=0.1)
    momentum = FloatParam("sgd momentum", default=0.9)
    weightDecay = FloatParam("adamw weight decay", default=1e-4)
    schedule = EnumParam(["constant", "cosine"], "lr schedule",
                         default="cosine")
    warmupSteps = IntParam("lr warmup steps", default=0)
    epochs = IntParam("training epochs", default=1)
    batchSize = IntParam("global batch size", default=128)
    seed = IntParam("rng seed", default=0)
    computeDtype = EnumParam(["float32", "bfloat16"],
                             "device compute dtype", default="bfloat16")
    meshAxes = DictParam("mesh axes, e.g. {'data': -1} or "
                         "{'data': 4, 'fsdp': 2}", default=None)
    paramSharding = EnumParam(["replicated", "fsdp"],
                              "parameter sharding strategy",
                              default="replicated")
    inputShape = UDFParam("reshape flat features to this per-row shape "
                          "(list), e.g. [32,32,3]", default=None)
    checkpointDir = StringParam("checkpoint directory ('' = off)", default="")
    checkpointEvery = IntParam("steps between checkpoints", default=200)
    resume = BoolParam("resume from latest checkpoint if present",
                       default=True)
    logEvery = IntParam("steps between loss logs", default=50)
    dataFeed = EnumParam(
        ["host", "device"],
        "'host' streams minibatches through a prefetch thread; 'device' "
        "places the whole (padded) dataset in HBM once and shuffles on "
        "device per epoch, so the steady-state step consumes only a "
        "scalar index from the host — the MXU-bound mode for datasets "
        "that fit in HBM (single-process, in-memory tables only)",
        default="host")
    profileDir = StringParam(
        "emit a jax.profiler xplane trace of the training loop here "
        "('' = off; SURVEY §5 profiler upgrade)", default="")
    memoryStatsEvery = IntParam(
        "steps between device-memory-stats samples (bytes_in_use/peak) "
        "recorded into learner.memory_samples and the fit trace "
        "(0 = off; device-feed mode samples once per chunk)", default=0)

    def _post_init(self):
        self._mesh: Optional[Mesh] = None
        self.history: List[Dict[str, float]] = []

    def set_mesh(self, mesh: Mesh) -> "TPULearner":
        self._mesh = mesh
        return self

    # -- internals ----------------------------------------------------------

    def _build_module(self):
        factory = self.get("moduleFactory")
        if factory is not None:
            return factory()
        spec = self.get("networkSpec")
        if spec is None:
            raise ValueError("set networkSpec or moduleFactory")
        spec = dict(spec)
        if self.get("computeDtype") == "bfloat16":
            spec.setdefault("dtype", "bfloat16")
        return build_network(spec)

    def _loss_fn(self, logits, y, w):
        kind = self.get("loss")
        if kind == "cross_entropy":
            losses = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y)
        elif kind == "token_cross_entropy":
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y)
            losses = per_tok.mean(axis=-1)
        else:  # mse
            pred = logits.astype(jnp.float32)
            if pred.ndim == 2 and pred.shape[-1] == 1:
                pred = pred[:, 0]
            losses = (pred - y.astype(jnp.float32)) ** 2
        return jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1.0)

    def fit(self, table) -> TPUModel:
        """``table`` is a DataTable, or — streaming ingestion for data
        that should not live in host RAM at once — a sequence of
        DataTable shards / a zero-arg callable returning an iterable of
        shards (re-invoked each epoch; shuffling is within-shard with
        remainder rows carried across shard boundaries). The HDFS-staged
        feed of the reference (CNTKLearner.scala:123-140) becomes a
        shard iterator."""
        mesh = self._mesh or mesh_lib.make_mesh(self.get("meshAxes"))
        module = self._build_module()
        input_shape = self.get("inputShape")
        fcol, lcol = self.get_features_col(), self.get_label_col()
        y_cast = np.int32 if self.get("loss") != "mse" else np.float32

        streaming = not isinstance(table, DataTable)
        if streaming:
            if not callable(table) and iter(table) is table:
                raise ValueError(
                    "streaming fit() needs to replay shards every epoch: "
                    "pass a sequence of DataTables, an io.ooc."
                    "ChunkedTable, or a zero-arg callable returning a "
                    "fresh iterator, not a one-shot generator")
            factory = table if callable(table) else (lambda: iter(table))
            # one metadata pass: count rows AND grab the first shard for
            # shapes/schema (IO-backed factories pay this pass once, not
            # twice). A ChunkedTable that already knows its row count
            # skips the counting decode pass entirely (spill-aware
            # feed: epochs then replay the chunk stream, each chunk
            # decoding on the prefetch worker while the device steps).
            from mmlspark_tpu.io.ooc import ChunkedTable as _Chunked
            if isinstance(table, _Chunked) and table.num_rows:
                n, first_shard = table.num_rows, table.peek()
            else:
                n, first_shard = 0, None
                for t in factory():
                    if first_shard is None:
                        first_shard = t
                    n += len(t)
            if n == 0:
                raise ValueError("empty shard stream")
            x0, y0 = table_to_xy(first_shard, fcol, lcol, input_shape)
            sample_x, sample_y = x0[:1], y0[:1].astype(y_cast)
            schema_src = first_shard
            x = y = None
        else:
            x, y = table_to_xy(table, fcol, lcol, input_shape)
            y = y.astype(y_cast)
            n = x.shape[0]
            sample_x, sample_y = x[:1], y[:1]
            schema_src = table

        # multi-host: each process feeds its LOCAL rows; the global batch
        # is assembled per-step from every host's slice (the
        # host-partitioned feeding that replaces HDFS staging + scp,
        # ref: CNTKLearner.scala:123-140 / CommandBuilders.scala:207-229).
        # The caller passes this host's shard (see
        # parallel.distributed.shard_table_for_host); shards must be
        # equal-sized across hosts so step counts agree.
        from mmlspark_tpu.parallel import distributed as dist
        proc_count = dist.host_info().process_count
        batch_size = self.get("batchSize")
        if proc_count > 1:
            if batch_size % proc_count:
                raise ValueError(
                    f"batchSize {batch_size} must divide evenly over "
                    f"{proc_count} processes")
            local_batch = batch_size // proc_count
            # agree on a common step count: ragged shards would make one
            # host enter a collective the others never reach. Truncate
            # every host to the global minimum row count — streaming
            # already counted its rows in the metadata pass, so the same
            # agreement covers ragged shard streams (each host caps its
            # per-epoch consumption at n_min; the batching then yields
            # identical step counts and batch shapes on every host).
            from jax.experimental import multihost_utils
            n_all = np.asarray(multihost_utils.process_allgather(
                np.asarray([n])))
            n_min = int(n_all.min())
            if n_min != n:
                logger.warning(
                    "host shards are unequal (%s); truncating to %d "
                    "rows per host so step counts agree",
                    n_all.ravel().tolist(), n_min)
                if not streaming:
                    x, y = x[:n_min], y[:n_min]
                n = n_min
        else:
            local_batch = batch_size
        device_feed = self.get("dataFeed") == "device"
        if device_feed and streaming:
            raise ValueError(
                "dataFeed='device' needs the whole dataset resident in "
                "HBM: pass an in-memory DataTable per process (use "
                "dataFeed='host' for shard streams)")
        steps_per_epoch = max(1, (n + local_batch - 1) // local_batch)
        total_steps = steps_per_epoch * self.get("epochs")

        tx = make_optimizer(
            self.get("optimizer"), self.get("learningRate"),
            momentum=self.get("momentum"),
            weight_decay=self.get("weightDecay"),
            schedule=self.get("schedule"),
            warmup_steps=self.get("warmupSteps"),
            total_steps=total_steps)

        seed = self.get("seed")
        in_dtype = jnp.int32 if getattr(module, "int_input", False) \
            else sample_x.dtype

        def init_state():
            # initial values depend on the input's shape only
            variables = module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros(sample_x.shape, in_dtype), train=False)
            return {
                "params": variables["params"],
                "opt_state": tx.init(variables["params"]),
                "batch_stats": variables.get("batch_stats", {}),
                "step": jnp.zeros((), jnp.int32),
            }

        # shardings: batch over data axis; state replicated or fsdp-sharded
        abstract_state = jax.eval_shape(init_state)
        has_bn = bool(abstract_state["batch_stats"])
        repl = NamedSharding(mesh, P())
        rule = (fsdp_sharding_rule(mesh)
                if (self.get("paramSharding") == "fsdp"
                    and mesh_lib.FSDP_AXIS in mesh.shape)
                else (lambda _: repl))
        state_sharding = jax.tree_util.tree_map(rule, abstract_state)
        # ONE program that writes every device's shards in place: the
        # unsharded state never exists on device 0 (under fsdp it may
        # not fit there — that is what fsdp is for)
        state = jax.jit(init_state, out_shardings=state_sharding)()

        data_sharding = {
            "x": NamedSharding(mesh, P(*((mesh_lib.DATA_AXIS,)
                                         + (None,) * (sample_x.ndim - 1)))),
            "y": NamedSharding(mesh, P(*((mesh_lib.DATA_AXIS,)
                                         + (None,) * (sample_y.ndim - 1)))),
            "w": NamedSharding(mesh, P(mesh_lib.DATA_AXIS)),
        }

        loss_kind = self.get("loss")
        is_int_input = bool(getattr(module, "int_input", False))
        dropout_seed = self.get("seed") + 1

        def train_step(st, batch):
            step_rng = jax.random.fold_in(
                jax.random.PRNGKey(dropout_seed), st["step"])

            def loss_of(p):
                inputs = batch["x"].astype(jnp.int32) if is_int_input \
                    else batch["x"]
                var_in = {"params": p}
                if has_bn:
                    var_in["batch_stats"] = st["batch_stats"]
                    out, mut = module.apply(
                        var_in, inputs, train=True,
                        mutable=["batch_stats"],
                        rngs={"dropout": step_rng})
                    new_bs = mut["batch_stats"]
                else:
                    out = module.apply(var_in, inputs, train=True,
                                       rngs={"dropout": step_rng})
                    new_bs = st["batch_stats"]
                loss = self._loss_fn(out, batch["y"], batch["w"])
                return loss, new_bs

            (loss, new_bs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(st["params"])
            updates, new_opt = tx.update(grads, st["opt_state"], st["params"])
            new_params = optax.apply_updates(st["params"], updates)
            return {
                "params": new_params,
                "opt_state": new_opt,
                "batch_stats": new_bs,
                "step": st["step"] + 1,
            }, loss

        def learner_step(st, batch):
            """``train_step`` under the name its program has in a
            profile (jit_learner_step)."""
            return train_step(st, batch)

        jit_step = jax.jit(learner_step,
                           in_shardings=(state_sharding, data_sharding),
                           out_shardings=(state_sharding, None),
                           donate_argnums=(0,))

        # checkpoint/resume. A corrupt/truncated checkpoint (a crash
        # mid-save, a filesystem hiccup) must not kill the whole fit:
        # fall back newest -> oldest across the retained checkpoints,
        # then to fresh init — resume is a best-effort accelerator, not
        # a correctness gate (losing a few hundred steps beats losing
        # the run).
        ckpt_dir = self.get("checkpointDir")
        start_step = 0
        if ckpt_dir and self.get("resume"):
            candidates = _checkpoint_candidates(ckpt_dir)
            for candidate in candidates:
                try:
                    loaded = _load_checkpoint_pytree(candidate)
                    # namedtuple containers (optax states) serialize as
                    # plain tuples; rebuild them against the
                    # freshly-built treedef. Unflatten/step parsing can
                    # fail on a truncated file too — same fallback.
                    host_state = jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(state),
                        jax.tree_util.tree_leaves(loaded))
                    cand_step = int(host_state["step"])
                    cand_state = jax.tree_util.tree_map(
                        lambda a, s: jax.device_put(jnp.asarray(a), s),
                        host_state, state_sharding)
                except OSError:
                    # transient I/O (network timeout, 5xx via the
                    # remote filesystems' IOError surface, connection
                    # reset) is NOT corruption: falling back here
                    # would silently restart a run from fresh init
                    # during a store outage — fail loudly instead (the
                    # filesystem layer already retried)
                    raise
                except Exception as e:  # noqa: BLE001 — corrupt ckpt
                    # parse-class failures (truncated npz, bad json,
                    # mismatched tree): genuinely a bad FILE
                    logger.warning(
                        "failed to load checkpoint %s (%s); falling "
                        "back to the previous one", candidate, e)
                    continue
                state = cand_state
                start_step = cand_step
                logger.info("resumed from %s (step %d)", candidate,
                            start_step)
                break
            else:
                if candidates:
                    logger.warning(
                        "no loadable checkpoint in %s; training from "
                        "fresh init", ckpt_dir)
        if proc_count > 1 and ckpt_dir and self.get("resume"):
            # hosts must resume from the SAME step — a host that found
            # no checkpoint (non-shared filesystem) would replay steps
            # the others skip and hang the first collective
            from jax.experimental import multihost_utils
            steps = np.asarray(multihost_utils.process_allgather(
                np.asarray([start_step]))).ravel()
            if len(set(steps.tolist())) > 1:
                raise RuntimeError(
                    f"hosts disagree on the resume step {steps.tolist()}:"
                    f" checkpointDir must be on a filesystem shared by "
                    f"all hosts (or set resume=False)")

        # training loop. Input feed: a background thread slices/pads the
        # next minibatch and device_puts it while the current step runs on
        # the MXU (the CNTK out-of-band reader analog — see utils/prefetch).
        # Logging NEVER syncs the device on the hot path: logged losses stay
        # on device and are flushed one logEvery-interval late, by which
        # time they are ready and float() is free.
        import time as _time
        from mmlspark_tpu.utils.prefetch import make_prefetcher

        self.history = []
        self.timing: Dict[str, float] = {}
        # fit-scoped trace: a span a host stage (dispatch, log flush,
        # checkpoint, feed wait) + optional device-memory samples, in
        # the same buffer the serving spans land in (span count capped
        # so a long fit can't balloon it)
        from mmlspark_tpu.core.trace import get_tracer, phase
        _tracer = get_tracer()
        fit_trace = _tracer.new_trace("learner.fit") \
            if _tracer.enabled else None
        _SPAN_CAP = 2048
        mem_every = int(self.get("memoryStatsEvery") or 0)
        self.memory_samples: List[Dict[str, Any]] = []

        def _capped_trace():
            if fit_trace is not None and \
                    len(fit_trace._spans) < _SPAN_CAP:
                return fit_trace
            return None

        def _stage(name, **attrs):
            """A host stage of the fit on the one stage clock
            (core.trace.phase): in the profiler's file always, in the
            fit trace while its cap lasts."""
            return phase(name, trace=_capped_trace(), **attrs)

        def _sample_memory(step_, force=False):
            if not mem_every or (not force and step_ % mem_every):
                return
            from mmlspark_tpu.utils.profiling import device_memory_stats
            stats = device_memory_stats()
            if not stats:
                return
            sample = {"step": int(step_)}
            for key in ("bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit"):
                if key in stats:
                    sample[key] = stats[key]
            self.memory_samples.append(sample)
            trace = _capped_trace()
            if trace is not None:
                _tracer.emit("memory", _time.perf_counter(),
                             attrs=sample, trace=trace)

        np_rng = np.random.default_rng(self.get("seed"))
        log_every = self.get("logEvery")
        ckpt_every = self.get("checkpointEvery")
        epochs = self.get("epochs")

        def index_stream():
            """(epoch, step, bx, by) numpy batches. In-memory mode
            shuffles globally per epoch; streaming mode re-reads the
            shard factory each epoch, shuffles within shards, and
            carries remainder rows across shard boundaries."""
            step = 0
            for epoch in range(epochs):
                if not streaming:
                    order = np_rng.permutation(n)
                    for bstart in range(0, n, local_batch):
                        step += 1
                        if step <= start_step:
                            continue  # fast-forward post-resume
                        idx = order[bstart:bstart + local_batch]
                        yield epoch, step, x[idx], y[idx]
                    continue
                carry_x = carry_y = None
                consumed = 0   # rows taken this epoch; capped at n so
                #                multi-host ragged streams stay in step
                for shard in factory():
                    if consumed >= n:
                        break
                    xs, ys = table_to_xy(shard, fcol, lcol, input_shape)
                    ys = ys.astype(y_cast)
                    take = min(len(xs), n - consumed)
                    if take < len(xs):
                        xs, ys = xs[:take], ys[:take]
                    consumed += take
                    perm = np_rng.permutation(len(xs))
                    xs, ys = xs[perm], ys[perm]
                    if carry_x is not None:
                        xs = np.concatenate([carry_x, xs])
                        ys = np.concatenate([carry_y, ys])
                    n_full = len(xs) // local_batch
                    for i in range(n_full):
                        step += 1
                        if step <= start_step:
                            continue
                        sl = slice(i * local_batch, (i + 1) * local_batch)
                        yield epoch, step, xs[sl], ys[sl]
                    rest = len(xs) - n_full * local_batch
                    carry_x = xs[-rest:] if rest else None
                    carry_y = ys[-rest:] if rest else None
                if carry_x is not None:
                    step += 1
                    if step > start_step:
                        yield epoch, step, carry_x, carry_y

        def _to_global(arr, sharding):
            """Local slice -> global device array. Single-process:
            plain device_put; multi-process: every host contributes its
            slice of the global batch."""
            if proc_count > 1:
                return jax.make_array_from_process_local_data(
                    sharding, arr)
            return jax.device_put(arr, sharding)

        def make_batch(item):
            epoch, step, bx_np, by_np = item
            bx, true_len = mesh_lib.pad_to_multiple(
                bx_np, local_batch, axis=0)
            by, _ = mesh_lib.pad_to_multiple(by_np, local_batch, axis=0)
            w = (np.arange(local_batch) < true_len).astype(np.float32)
            return epoch, step, true_len * proc_count, {
                "x": _to_global(bx, data_sharding["x"]),
                "y": _to_global(by, data_sharding["y"]),
                "w": _to_global(w, data_sharding["w"]),
            }

        pending: List[Tuple[int, int, Any, float]] = []  # deferred log queue

        def flush_logs(final: bool = False) -> None:
            # flush entries whose device value is (almost surely) ready:
            # everything but the newest, or everything when final
            keep = 0 if final else 1
            if len(pending) <= keep:
                return
            # the read of a loss blocks until its step has run
            with _stage("learner.flush_logs", entries=len(pending) - keep):
                while len(pending) > keep:
                    step_, epoch_, dev_loss, t = pending.pop(0)
                    if isinstance(dev_loss, tuple):
                        # device-feed chunks log (loss_vector, index);
                        # resolve via a plain transfer — indexing with
                        # jnp would compile an eager gather mid-loop
                        arr, j = dev_loss
                        lv = float(np.asarray(arr)[j])
                    else:
                        lv = float(dev_loss)
                    self.history.append({"step": step_, "loss": lv,
                                         "epoch": epoch_, "time": t})
                    logger.info("step %d/%d loss %.4f", step_,
                                total_steps, lv)

        from mmlspark_tpu.utils.profiling import maybe_trace

        global_step = start_step
        t_first = None
        t_loop_start = _time.time()
        first_timed_step = start_step
        examples_timed = 0   # true (unpadded) rows after the warmup step
        # CPU backend: async dispatch racing ahead starves XLA's
        # in-process collective rendezvous on small hosts (7/8 devices
        # join, the 8th's thunk never gets a pool thread -> fatal
        # timeout). Serialize steps there; TPU keeps async dispatch.
        sync_each_step = jax.default_backend() == "cpu"

        def step_bookkeeping(loss, true_rows, epoch):
            """Per-step timing/logging/checkpoint shared by both feed
            modes (reads global_step/state from the enclosing scope)."""
            nonlocal t_first, first_timed_step, examples_timed
            if sync_each_step:
                loss.block_until_ready()
            if t_first is None:
                # timing starts after the compile+first step
                loss.block_until_ready()
                t_first = _time.time()
                first_timed_step = global_step
            else:
                examples_timed += true_rows
            if global_step % log_every == 0 or global_step == total_steps:
                pending.append((global_step, epoch, loss, _time.time()))
                flush_logs()
            if ckpt_dir and global_step % ckpt_every == 0:
                save_checkpoint()

        def save_checkpoint():
            with _stage("learner.checkpoint", step=global_step):
                _save_checkpoint(ckpt_dir, global_step, state)

        if device_feed:
            # Pad once to full batches; per-epoch shuffle happens ON
            # DEVICE: a permutation derived on device from the (shared)
            # seed key gathers the padded dataset into an
            # (steps, batch, ...) epoch tensor, and each step then reads
            # only a scalar batch index from the host — the steady state
            # is chip-bound, not feed-bound. Multi-host: every process
            # contributes its LOCAL padded shard to a row-sharded global
            # array; the permutation key is seed-derived in-program so
            # hosts agree without communicating, and the global gather's
            # cross-device row movement rides the mesh interconnect
            # (ref: CommandBuilders.scala:108-267 — distributed training
            # is the product, not a mode).
            n_pad_local = steps_per_epoch * local_batch
            pad = n_pad_local - n
            if pad:
                x_p = np.concatenate(
                    [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
                y_p = np.concatenate(
                    [y, np.zeros((pad,) + y.shape[1:], y.dtype)])
            else:
                x_p, y_p = x, y
            w_p = (np.arange(n_pad_local) < n).astype(np.float32)
            n_pad = n_pad_local * proc_count     # GLOBAL padded rows
            global_batch = local_batch * proc_count
            # the CPU backend reports no stats (nothing to guard); a
            # process can only ask its own devices
            stats = jax.local_devices()[0].memory_stats()
            hbm_limit = stats["bytes_limit"] if stats else None
            # resident twice: the row-major copy + the epoch tensor. Only
            # the data axis shards the rows — other mesh axes replicate
            # them, so per-chip residency divides by the data size alone.
            want = 2 * proc_count * (x_p.nbytes + y_p.nbytes + w_p.nbytes)
            per_chip = want / mesh.shape.get(mesh_lib.DATA_AXIS, 1)
            if hbm_limit and per_chip > 0.6 * hbm_limit:
                logger.warning(
                    "dataFeed='device' will hold ~%.1f GB per chip in HBM "
                    "(limit %.1f GB/chip); consider dataFeed='host'",
                    per_chip / 2**30, hbm_limit / 2**30)

            def _row_sh(nd):
                return NamedSharding(mesh, P(*((mesh_lib.DATA_AXIS,)
                                               + (None,) * (nd - 1))))

            x_dev = _to_global(x_p, _row_sh(x_p.ndim))
            y_dev = _to_global(y_p, _row_sh(y_p.ndim))
            w_dev = _to_global(w_p, _row_sh(1))
            row_shardings = (_row_sh(x_p.ndim), _row_sh(y_p.ndim),
                             _row_sh(1))
            base_key = jax.random.PRNGKey(self.get("seed") + 17)

            def run_chunk(st, xf, yf, wf, epoch_s, start, length):
                """``length`` consecutive steps as ONE device program:
                the epoch permutation is derived on device from the epoch
                index (fold_in — deterministic, so resume replays it),
                the shuffled epoch tensors never exist on the host, and
                the scan body reads one batch per step. The host
                dispatches once per chunk with two scalars, so tiny
                step times can't become host-dispatch-bound (the loop
                must not contain eager ops: each would compile
                mid-loop)."""
                perm = jax.random.permutation(
                    jax.random.fold_in(base_key, epoch_s), n_pad)
                # gather ONLY this chunk's rows (checkpoint-segmented
                # chunks would otherwise re-gather the full epoch tensor
                # once per segment)
                sel = jax.lax.dynamic_slice_in_dim(
                    perm, start * global_batch, length * global_batch)

                def g(a):
                    return a[sel].reshape(
                        (length, global_batch) + a.shape[1:])
                xs, ys, ws = g(xf), g(yf), g(wf)

                def body(carry, b):
                    batch = {"x": xs[b], "y": ys[b], "w": ws[b]}
                    return train_step(carry, batch)
                st, losses = jax.lax.scan(
                    body, st, jnp.arange(length))
                # true (unpadded) rows this chunk — padding carries w=0
                cnt = (ws > 0).sum()
                return st, losses, cnt

            chunk_fns: Dict[int, Any] = {}   # scan length -> jitted fn

            def get_chunk_fn(length):
                if length not in chunk_fns:
                    # named for its program in a profile
                    # (jit_learner_chunk)
                    def learner_chunk(st, xf, yf, wf, e, s0, _len=length):
                        return run_chunk(st, xf, yf, wf, e, s0, _len)
                    chunk_fns[length] = jax.jit(
                        learner_chunk,
                        in_shardings=(state_sharding,) + row_shardings
                        + (None, None),
                        out_shardings=(state_sharding, None, None),
                        donate_argnums=(0,))
                return chunk_fns[length]

            # (device count scalar, counted-in-steady-state?) per chunk;
            # resolved after the clock stops
            chunk_counts: List[Tuple[Any, bool]] = []

            def chunk_bookkeeping(losses, cnt, length, epoch):
                """Chunk analog of step_bookkeeping. All values stay on
                device; the only host interaction is np.asarray transfers
                (never eager jnp ops, which would compile mid-loop)."""
                nonlocal t_first, first_timed_step
                if sync_each_step or t_first is None:
                    losses.block_until_ready()
                chunk_counts.append((cnt, t_first is not None))
                if t_first is None:
                    # timing starts after the compile+first chunk
                    t_first = _time.time()
                    first_timed_step = global_step
                base = global_step - length
                for j in range(length):
                    gs = base + j + 1
                    if gs % log_every == 0 or gs == total_steps:
                        pending.append(
                            (gs, epoch, (losses, j), _time.time()))
                flush_logs()
                if ckpt_dir and global_step % ckpt_every == 0:
                    save_checkpoint()

            # steps trace under the mesh: kernels that XLA cannot
            # partition (ring_attention.flash_per_shard) read it
            with maybe_trace(self.get("profileDir")), jax.set_mesh(mesh):
                for epoch in range(epochs):
                    if (epoch + 1) * steps_per_epoch <= start_step:
                        global_step = (epoch + 1) * steps_per_epoch
                        continue
                    base = epoch * steps_per_epoch
                    i = max(0, start_step - base)   # resume mid-epoch
                    while i < steps_per_epoch:
                        seg_end = steps_per_epoch
                        if ckpt_dir:
                            # segment at checkpoint boundaries so saves
                            # land exactly every checkpointEvery steps
                            cur = base + i
                            nxt = (cur // ckpt_every + 1) * ckpt_every
                            seg_end = min(seg_end, nxt - base)
                        length = seg_end - i
                        fn = get_chunk_fn(length)
                        # the dispatch alone (chunks run async): the
                        # span shows host-side stalls, the profile's
                        # device rows the on-chip time
                        with _stage("learner.chunk", step=base + seg_end,
                                    epoch=epoch, length=length):
                            state, losses, cnt = fn(
                                state, x_dev, y_dev, w_dev,
                                np.int32(epoch), np.int32(i))
                        global_step = base + seg_end
                        chunk_bookkeeping(losses, cnt, length, epoch)
                        _sample_memory(global_step, force=bool(mem_every))
                        i = seg_end
        else:
            feed = make_prefetcher(index_stream(), make_batch, depth=2)
            batches = iter(feed)
            try:
                with maybe_trace(self.get("profileDir")), \
                        jax.set_mesh(mesh):
                    while True:
                        # blocked on the prefetcher
                        with _stage("learner.feed_wait"):
                            item = next(batches, None)
                        if item is None:
                            break
                        epoch, global_step, true_len, batch = item
                        # dispatch-enqueue wall (steps run async): the
                        # span shows host-side stalls, the profile's
                        # device rows the on-chip time
                        with _stage("learner.step", step=global_step,
                                    epoch=epoch):
                            state, loss = jit_step(state, batch)
                        step_bookkeeping(loss, true_len, epoch)
                        _sample_memory(global_step)
            finally:
                # abnormal exit must not leave the worker blocked in put()
                # pinning prefetched batches in HBM
                feed.close()
        with _stage("learner.final_wait"):
            state = jax.block_until_ready(state)
        t_end = _time.time()
        if device_feed:
            # resolve the deferred per-chunk row counts (transfers only,
            # after the clock stops so they can't skew the measurement).
            # Counts are GLOBAL already — the chunk's w spans every
            # host's rows — so no per-process multiplier.
            examples_timed = int(sum(
                float(np.asarray(c)) for c, timed in chunk_counts
                if timed))
            if t_first is not None and global_step == first_timed_step:
                # single-chunk run: the whole fit was "warmup", so report
                # the full wall including the first chunk (compile time
                # excluded is impossible here — flag it)
                examples_timed = int(sum(
                    float(np.asarray(c)) for c, _ in chunk_counts))
                first_timed_step = start_step
                t_first = t_loop_start
                self_timing_includes_compile = True
            else:
                self_timing_includes_compile = False
        else:
            self_timing_includes_compile = False
        flush_logs(final=True)
        steps_timed = global_step - (first_timed_step if t_first else 0)
        if t_first is not None and steps_timed > 0:
            wall = t_end - t_first
            self.timing = {
                "steps_timed": steps_timed,
                "wall_s": wall,
                # true rows only — padding of partial batches is masked
                # compute, counting it would inflate the metric
                "examples_per_sec": examples_timed / max(wall, 1e-9),
            }
            if self_timing_includes_compile:
                self.timing["includes_compile"] = True
        if ckpt_dir:
            save_checkpoint()
        if fit_trace is not None:
            fit_trace.root.set("steps", int(global_step))
            fit_trace.root.set("feed",
                               "device" if device_feed else "host")
            if self.timing:
                fit_trace.root.set(
                    "examples_per_sec",
                    round(self.timing.get("examples_per_sec", 0.0), 1))
            _tracer.finish(fit_trace)

        host_params = jax.device_get(state["params"])
        host_bs = jax.device_get(state["batch_stats"])
        weights = {"params": host_params}
        if has_bn:
            weights["batch_stats"] = host_bs
        field = schema_src.schema.get(self.get_features_col())
        img_scale = (1.0 / 255.0) if (field is not None
                                      and ImageSchema.is_image(field)) else 1.0
        model = TPUModel(
            modelFn=_InferApply(module, is_int_input, img_scale, input_shape),
            weights=weights,
            inputCol=self.get_features_col(),
            outputCol="scores",
            batchSize=batch_size,
            computeDtype="float32")
        model.set_mesh(mesh)
        return model


class _InferApply:
    """Picklable inference apply for trained modules (handles batch_stats
    and integer-token inputs)."""

    def __init__(self, module, int_input: bool = False, scale: float = 1.0,
                 input_shape=None):
        self.module = module
        self.int_input = int_input
        self.scale = scale
        self.input_shape = input_shape

    def __call__(self, weights, inputs):
        x = list(inputs.values())[0]
        if self.input_shape:
            x = x.reshape((x.shape[0],) + tuple(self.input_shape))
        if self.int_input:
            x = x.astype(jnp.int32)
        elif self.scale != 1.0:
            x = x.astype(jnp.float32) * self.scale
        variables = {"params": weights["params"]}
        if "batch_stats" in weights and weights["batch_stats"]:
            variables["batch_stats"] = weights["batch_stats"]
        return self.module.apply(variables, x, train=False)


def _is_remote(path: str) -> bool:
    from mmlspark_tpu.utils import filesystem as fslib
    return fslib.scheme_of(path) != "file"


def _remote_steps(ckpt_dir: str) -> List[str]:
    """Sorted step_XXXXXXXX names that have a COMPLETE checkpoint
    (treedef.json is uploaded last, so its presence marks done)."""
    import re
    from mmlspark_tpu.utils import filesystem as fslib
    fs = fslib.get_filesystem(ckpt_dir)
    steps = set()
    for f in fs.list_files(ckpt_dir.rstrip("/"), recursive=True):
        m = re.search(r"(step_\d{8})/treedef\.json$", f)
        if m:
            steps.add(m.group(1))
    return sorted(steps)


def _save_checkpoint(ckpt_dir: str, step: int, state) -> None:
    # multi-host: only the coordinator writes (hosts share the FS —
    # which may be a remote scheme like webdav://, the HDFS-staging
    # analog of CNTKLearner.scala:18-67 dataTransfer=hdfs)
    if jax.process_index() != 0:
        return
    host = jax.device_get(state)
    if _is_remote(ckpt_dir):
        import tempfile
        from mmlspark_tpu.utils import filesystem as fslib
        fs = fslib.get_filesystem(ckpt_dir)
        base = f"{ckpt_dir.rstrip('/')}/step_{step:08d}"
        with tempfile.TemporaryDirectory() as td:
            ser._save_pytree(host, td)
            # treedef.json LAST: it is the completeness marker that
            # _remote_steps / resume key on
            names = sorted(os.listdir(td),
                           key=lambda n: n == "treedef.json")
            for fn in names:
                with open(os.path.join(td, fn), "rb") as f:
                    fs.write_bytes(f"{base}/{fn}", f.read())
        try:
            stales = _remote_steps(ckpt_dir)[:-3]
            for stale in stales:
                fs.delete_path(f"{ckpt_dir.rstrip('/')}/{stale}/")
        except (IOError, OSError, NotImplementedError):
            pass                       # pruning (incl. listing) is
            #                            best-effort — the save landed
        return
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    os.makedirs(path, exist_ok=True)
    ser._save_pytree(host, path)
    # keep only the 3 latest
    all_ckpts = sorted(d for d in os.listdir(ckpt_dir)
                       if d.startswith("step_"))
    for stale in all_ckpts[:-3]:
        import shutil
        shutil.rmtree(os.path.join(ckpt_dir, stale), ignore_errors=True)


def _checkpoint_candidates(ckpt_dir: str) -> List[str]:
    """All retained checkpoint paths, NEWEST first — the corrupt-
    checkpoint fallback order (resume tries each until one loads). A
    remote LISTING failure propagates (the filesystem layer already
    retries): an unreachable store must fail loudly, not silently
    restart training from scratch — only corrupt checkpoint FILES get
    the fallback treatment."""
    if _is_remote(ckpt_dir):
        steps = _remote_steps(ckpt_dir)
        return [f"{ckpt_dir.rstrip('/')}/{s}" for s in reversed(steps)]
    if not os.path.isdir(ckpt_dir):
        return []
    ckpts = sorted((d for d in os.listdir(ckpt_dir)
                    if d.startswith("step_")), reverse=True)
    return [os.path.join(ckpt_dir, d) for d in ckpts]


def _latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    candidates = _checkpoint_candidates(ckpt_dir)
    return candidates[0] if candidates else None


def _load_checkpoint_pytree(path: str):
    """ser._load_pytree from a local OR remote checkpoint directory."""
    if not _is_remote(path):
        return ser._load_pytree(path)
    import tempfile
    from mmlspark_tpu.utils import filesystem as fslib
    fs = fslib.get_filesystem(path)
    with tempfile.TemporaryDirectory() as td:
        for fn in ("leaves.npz", "treedef.json"):
            data = fs.read_bytes(f"{path.rstrip('/')}/{fn}")
            with open(os.path.join(td, fn), "wb") as f:
                f.write(data)
        return ser._load_pytree(td)
