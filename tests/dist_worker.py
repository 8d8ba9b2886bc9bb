"""Worker process for the multi-process jax.distributed test.

Launched N times by tests/test_distributed.py — the TPU-native analog of
the reference's distributed-without-a-cluster pattern (ref:
LightGBMUtils.scala:110-118 local[*] partitions-as-nodes; SURVEY §4):
real separate processes rendezvous at a coordinator, assemble one global
device mesh, and run a psum across it.

Usage: python dist_worker.py <coordinator_port> <process_id> <n_processes>
"""

import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# CPU backend with 2 virtual devices per process, configured before any
# backend use
from mmlspark_tpu.utils.jax_compat import set_cpu_device_count  # noqa: E402

set_cpu_device_count(2)


def main() -> None:
    port, pid, nproc = (int(a) for a in sys.argv[1:4])

    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.parallel import distributed as dist

    info = dist.initialize(f"127.0.0.1:{port}", num_processes=nproc,
                           process_id=pid)
    assert info.process_count == nproc, info
    assert info.global_device_count == 2 * nproc, info
    assert info.is_coordinator == (pid == 0)

    # host-partitioned feeding: each process keeps its own row range
    # (replaces HDFS staging + scp, ref: CNTKLearner.scala:123-140)
    n_rows = 4 * nproc
    table = DataTable({"x": np.arange(n_rows, dtype=np.float64)})
    local = dist.shard_table_for_host(table, info)
    local_x = np.asarray(local["x"], dtype=np.float32)
    print(f"SHARD {pid} {','.join(str(int(v)) for v in local_x)}",
          flush=True)

    # one global mesh over every device of every process; psum rides the
    # collective backend exactly like histogram/gradient allreduce
    mesh = Mesh(np.array(jax.devices()), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    global_x = jax.make_array_from_process_local_data(sharding, local_x)

    total = jax.jit(shard_map(
        lambda v: lax.psum(jnp.sum(v), "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P()))(global_x)
    print(f"PSUM {pid} {float(total):.1f}", flush=True)

    # host-sharded TPULearner training across the processes: each host
    # feeds its local rows, the global batch is assembled per step via
    # make_array_from_process_local_data, gradients allreduce over the
    # global mesh (the mpirun-cntk analog, CommandBuilders.scala:241)
    from mmlspark_tpu.models.learner import TPULearner

    rng = np.random.default_rng(7)   # same global data on every host
    gx = rng.normal(size=(64, 6)).astype(np.float32)
    gy = (gx[:, 0] + gx[:, 1] > 0).astype(np.int64)
    full = DataTable({"features": gx, "label": gy})
    local = dist.shard_table_for_host(full, info)

    learner = TPULearner(
        networkSpec={"type": "mlp", "features": [8], "num_classes": 2},
        epochs=6, batchSize=8 * nproc, learningRate=0.1,
        computeDtype="float32", logEvery=1000,
        meshAxes={"data": info.global_device_count})
    model = learner.fit(local)
    # every host must end with IDENTICAL (replicated) trained params
    leaf = np.asarray(jax.tree_util.tree_leaves(
        model.get("weights"))[0]).ravel()[:3]
    print(f"TRAIN {pid} {','.join(f'{v:.6f}' for v in leaf)}", flush=True)

    # DEVICE-RESIDENT multi-host feed: each process device_puts its local
    # shard into a row-sharded global array; the epoch permutation is
    # derived on device from the shared seed key so hosts agree without
    # communicating (learner.py run_chunk). Every host must end with
    # identical replicated params, and a re-run with the same seed must
    # reproduce them exactly (on-device shuffle determinism).
    def fit_device_feed():
        dl = TPULearner(
            networkSpec={"type": "mlp", "features": [8], "num_classes": 2},
            epochs=6, batchSize=8 * nproc, learningRate=0.1,
            computeDtype="float32", logEvery=1000, dataFeed="device",
            meshAxes={"data": info.global_device_count})
        dmodel = dl.fit(local)
        return np.concatenate([
            np.asarray(leaf_arr).ravel()
            for leaf_arr in jax.tree_util.tree_leaves(
                dmodel.get("weights"))])

    dw1 = fit_device_feed()
    dw2 = fit_device_feed()
    det = int(np.array_equal(dw1, dw2))
    print(f"DEVFEED {pid} {','.join(f'{v:.6f}' for v in dw1[:3])},{det}",
          flush=True)

    # STREAMING multi-host: each host feeds a RAGGED shard stream (40 vs
    # 36 rows); hosts allgather their counts and truncate to the global
    # minimum so step counts agree (VERDICT r2 item 5 — the restriction
    # learner.py used to raise NotImplementedError for)
    my_rows = 40 if pid == 0 else 40 - 4 * pid
    lo = sum(40 if q == 0 else 40 - 4 * q for q in range(pid))
    rows = np.arange(lo, lo + my_rows)
    sx = gx[rows % 64]
    sy = gy[rows % 64]
    shards = [DataTable({"features": sx[k:k + 16], "label": sy[k:k + 16]})
              for k in range(0, my_rows, 16)]
    stream_learner = TPULearner(
        networkSpec={"type": "mlp", "features": [8], "num_classes": 2},
        epochs=4, batchSize=8 * nproc, learningRate=0.1,
        computeDtype="float32", logEvery=1000,
        meshAxes={"data": info.global_device_count})
    smodel = stream_learner.fit(shards)
    leaf = np.asarray(jax.tree_util.tree_leaves(
        smodel.get("weights"))[0]).ravel()[:3]
    print(f"STREAM {pid} {','.join(f'{v:.6f}' for v in leaf)}", flush=True)

    # multi-host GBDT: every process feeds its LOCAL row shard; bin
    # boundaries come from allgathered samples and histograms psum over
    # the global mesh (the LightGBM worker-partition + allreduce-ring
    # flow, ref: TrainUtils.scala:188-214). Hosts must grow IDENTICAL
    # forests.
    import hashlib
    from mmlspark_tpu.gbdt.booster import train as gbdt_train

    grng = np.random.default_rng(11)
    GX = grng.normal(size=(400, 6))
    GY = (GX[:, 0] + 0.5 * GX[:, 1] > 0).astype(float)
    rows_lo, rows_hi = pid * 200, (pid + 1) * 200
    booster = gbdt_train(
        {"objective": "binary", "num_iterations": 5, "num_leaves": 7,
         "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "data",
         "hist_method": "scatter"},
        GX[rows_lo:rows_hi], GY[rows_lo:rows_hi])
    digest = hashlib.sha256(
        booster.model_to_string().encode()).hexdigest()[:16]
    auc_ok = int(np.mean((booster.predict(GX) > 0.5) == GY) > 0.9)
    print(f"GBDT {pid} {digest},{auc_ok}", flush=True)

    # multi-host FEATURE-parallel: every process holds the FULL dataset
    # (LightGBM's feature-parallel layout) and owns a feature shard of
    # the global mesh; forests must be byte-identical across hosts
    # (ref: TrainParams.scala:26 tree_learner=feature across executors)
    fp = gbdt_train(
        {"objective": "binary", "num_iterations": 5, "num_leaves": 7,
         "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "feature",
         "hist_method": "scatter"},
        GX, GY)
    fp_digest = hashlib.sha256(
        fp.model_to_string().encode()).hexdigest()[:16]
    fp_ok = int(np.mean((fp.predict(GX) > 0.5) == GY) > 0.9)
    print(f"FPGBDT {pid} {fp_digest},{fp_ok}", flush=True)

    # multi-host VOTING-parallel: local row shards like data-parallel,
    # candidate-sized per-split collective (PV-tree across hosts)
    vt = gbdt_train(
        {"objective": "binary", "num_iterations": 5, "num_leaves": 7,
         "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "voting",
         "top_k": 6, "hist_method": "scatter"},
        GX[rows_lo:rows_hi], GY[rows_lo:rows_hi])
    vt_digest = hashlib.sha256(
        vt.model_to_string().encode()).hexdigest()[:16]
    vt_ok = int(np.mean((vt.predict(GX) > 0.5) == GY) > 0.9)
    print(f"VOTEGBDT {pid} {vt_digest},{vt_ok}", flush=True)

    # multi-host feature-parallel with SPARSE input: the dataset digest
    # hashes the CSR buffers (densifying would defeat the sparse path);
    # forests must still be byte-identical across hosts
    from mmlspark_tpu.core.sparse import CSRMatrix
    dense_for_csr = GX.copy()
    dense_for_csr[np.abs(dense_for_csr) < 0.6] = 0.0   # ~45% sparse
    csr_X = CSRMatrix.from_dense(dense_for_csr.astype(np.float32))
    fps = gbdt_train(
        {"objective": "binary", "num_iterations": 4, "num_leaves": 7,
         "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "feature",
         "hist_method": "scatter"},
        csr_X, GY)
    fps_digest = hashlib.sha256(
        fps.model_to_string().encode()).hexdigest()[:16]
    # 0.80 floor: zeroing |x|<0.6 costs signal — single-process serial
    # training on the same CSR data also lands at 0.8275
    fps_ok = int(np.mean((fps.predict(csr_X) > 0.5) == GY) > 0.80)
    print(f"FPCSR {pid} {fps_digest},{fps_ok}", flush=True)

    # f64-faithful multi-host binning: a feature at 2^24 scale whose
    # distinct values collapse under an f32 wire. The agreed boundaries
    # must equal a single-host f64 BinMapper fit on the concatenated
    # data byte-for-byte (the parent test recomputes and compares), and
    # the trained forests must agree across hosts with f32_unsafe set.
    from mmlspark_tpu.gbdt.booster import _multihost_mapper
    f24 = 2.0 ** 24
    UX = np.stack([
        f24 + np.arange(400, dtype=np.float64) * 0.25,   # f32-unsafe
        grng.normal(size=400)], axis=1)
    UY = ((UX[:, 0] - f24) * 0.04 + UX[:, 1] > 5.0).astype(float)
    u_mapper = _multihost_mapper(UX[rows_lo:rows_hi], False, 15, 2, nproc)
    b_digest = hashlib.sha256(
        b"".join(u.tobytes() for u in u_mapper.upper_bounds)
    ).hexdigest()[:16]
    ub = gbdt_train(
        {"objective": "binary", "num_iterations": 4, "num_leaves": 7,
         "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "data",
         "hist_method": "scatter"},
        UX[rows_lo:rows_hi], UY[rows_lo:rows_hi])
    u_digest = hashlib.sha256(
        ub.model_to_string().encode()).hexdigest()[:16]
    unsafe = int(bool(ub.params.get("f32_unsafe")))
    print(f"F64BIN {pid} {b_digest},{u_digest},{unsafe}", flush=True)

    # multi-host checkpoint/resume on a REMOTE (webdav://) filesystem:
    # the coordinator writes checkpoints over HTTP PUT, every host
    # resumes from the same remote step (the shared-FS requirement
    # learner.py:452-463 enforces — previously only file:// could
    # satisfy it; ref: CNTKLearner.scala:18-67 dataTransfer=hdfs)
    if len(sys.argv) > 4 and sys.argv[4].startswith("webdav://"):
        from mmlspark_tpu.models.learner import _latest_checkpoint
        ck = f"{sys.argv[4]}/ckpt"
        mk = lambda epochs: TPULearner(  # noqa: E731
            networkSpec={"type": "mlp", "features": [8],
                         "num_classes": 2},
            epochs=epochs, batchSize=8 * nproc, learningRate=0.1,
            computeDtype="float32", logEvery=1000,
            checkpointDir=ck, checkpointEvery=2, resume=True,
            meshAxes={"data": info.global_device_count})
        mk(2).fit(local)
        latest = _latest_checkpoint(ck)       # visible from EVERY host
        step1 = int(latest.rsplit("step_", 1)[1]) if latest else -1
        m2 = mk(4).fit(local)                 # resumes mid-training
        leaf = np.concatenate([
            np.asarray(a).ravel()
            for a in jax.tree_util.tree_leaves(m2.get("weights"))])
        wd_digest = hashlib.sha256(
            np.round(leaf, 6).tobytes()).hexdigest()[:16]
        print(f"WEBDAVCKPT {pid} {wd_digest},{step1}", flush=True)

    print(f"OK {pid}", flush=True)


if __name__ == "__main__":
    main()
