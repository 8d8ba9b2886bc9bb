"""Launch helper for the multi-host fabric drills: one member of a
2-process ``jax.distributed`` group on this box.

Spawned N times by tests/test_multihost_fabric.py. Each member
rendezvouses through ``parallel.distributed.initialize`` — the REAL
coordinator/worker path
with the bounded timeout and gloo CPU collectives — then runs the two
fabric drills end-to-end:

- PR 15's ``bin_fit='sketch'`` multi-host GBDT fit on disjoint row
  shards streamed as an out-of-core Arrow ``ChunkedTable`` (the PR 18
  ingest composed under a REAL process group): forest must come out
  bit-identical on every host, and bit-identical to the parent's
  single-group in-memory oracle replay;
- the PR 19 quantized reduce-scatter drill: the SAME stream retrained
  at ``hist_bits=16, hist_comm='reduce_scatter'`` — bit-identical
  across hosts, and the modeled collective wire (``COMM`` lines) must
  come out >=2x under the f32 psum run's;
- a PR 14-shape explicit-shardings serving jit over the GLOBAL mesh
  (in_shardings/out_shardings declared, batch dim sharded across the
  processes' devices).

Usage::

    python multihost_worker.py <coordinator_port> <process_id> <nproc>
        [--timeout-s T] [--die-before-rendezvous]

``--die-before-rendezvous`` makes a non-coordinator member exit before
ever calling initialize() — the member-death drill: the SURVIVING member
must get a clean ProcessGroupError within the bounded timeout (exit code
7) instead of hanging.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("MMLSPARK_TPU_TEST_MODE", "1")

import jax  # noqa: E402

# CPU backend, ONE device per process: the global mesh is assembled
# across processes
from mmlspark_tpu.utils.jax_compat import set_cpu_device_count  # noqa: E402

set_cpu_device_count(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("port", type=int)
    ap.add_argument("process_id", type=int)
    ap.add_argument("nproc", type=int)
    ap.add_argument("--timeout-s", type=float, default=60.0)
    ap.add_argument("--die-before-rendezvous", action="store_true")
    args = ap.parse_args()
    pid, nproc = args.process_id, args.nproc

    from mmlspark_tpu.parallel import distributed as dist

    if args.die_before_rendezvous and pid != 0:
        # the dead member: never shows up at the coordinator
        print(f"DIED {pid}", flush=True)
        sys.exit(3)

    t0 = time.monotonic()
    try:
        info = dist.initialize(f"127.0.0.1:{args.port}",
                               num_processes=nproc, process_id=pid,
                               timeout_s=args.timeout_s)
    except dist.ProcessGroupError as e:
        wall = time.monotonic() - t0
        print(f"GROUP_ERROR {pid} {wall:.1f} {type(e).__name__}",
              flush=True)
        sys.exit(7)
    assert info.process_count == nproc, info
    assert info.is_coordinator == (pid == 0), info
    assert dist.in_process_group() == (nproc > 1)
    dist.require_process_group(nproc)   # the multi-machine floor gate

    import hashlib

    import numpy as np

    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.gbdt.booster import train as gbdt_train
    from mmlspark_tpu.io.ooc import ChunkedTable

    def _comm_line(tag, booster):
        cb = booster.train_info.get("comm_bytes", {})
        print(f"COMM {pid} {tag} {cb.get('psum', 0)} "
              f"{cb.get('psum_scatter', 0)} {cb.get('all_gather', 0)}",
              flush=True)

    # -- drill 1: multi-host sketch-binned GBDT on disjoint row shards,
    # streamed through the out-of-core ChunkedTable ingest (PR 18's
    # path composed under a REAL group). Every host replays its LOCAL
    # 200 rows as two 100-row chunks; bin boundaries are agreed through
    # the allgathered quantile-sketch summaries; histograms psum over
    # the global mesh. The forest must be bit-identical on every host
    # AND to the parent's single-group in-memory oracle (same merged
    # sketches, same global row order).
    grng = np.random.default_rng(11)
    GX = grng.normal(size=(400, 6))
    GY = (GX[:, 0] + 0.5 * GX[:, 1] > 0).astype(float)
    lo, hi = pid * 200, (pid + 1) * 200

    def _local_chunks():
        for k in (lo, lo + 100):
            yield DataTable({"features": GX[k:k + 100],
                             "label": GY[k:k + 100]})

    base_params = {
        "objective": "binary", "num_iterations": 5, "num_leaves": 7,
        "max_bin": 15, "min_data_in_leaf": 5, "parallelism": "data",
        "hist_method": "scatter", "bin_fit": "sketch"}
    booster = gbdt_train(
        base_params, ChunkedTable.from_generator(_local_chunks))
    digest = hashlib.sha256(
        booster.model_to_string().encode()).hexdigest()[:16]
    bin_digest = hashlib.sha256(
        b"".join(u.tobytes()
                 for u in booster.bin_mapper.upper_bounds)
    ).hexdigest()[:16]
    acc_ok = int(np.mean((booster.predict(GX) > 0.5) == GY) > 0.9)
    print(f"DIGEST {pid} {digest} {bin_digest} {acc_ok}", flush=True)
    _comm_line("f32", booster)

    # -- drill 1b: the SAME stream retrained on the quantized
    # reduce-scatter engine (PR 19). Integer histogram accumulation
    # makes the forest exactly reproducible across the group, and the
    # modeled wire must come out >=2x under the f32 psum run's.
    qbooster = gbdt_train(
        {**base_params, "hist_bits": 16, "hist_comm": "reduce_scatter"},
        ChunkedTable.from_generator(_local_chunks))
    qdigest = hashlib.sha256(
        qbooster.model_to_string().encode()).hexdigest()[:16]
    qacc_ok = int(np.mean((qbooster.predict(GX) > 0.5) == GY) > 0.9)
    print(f"QDIGEST {pid} {qdigest} {qacc_ok}", flush=True)
    _comm_line("q16", qbooster)

    # -- drill 2: explicit-shardings serving jit UNDER the group (the
    # PR 14 jit shape: shardings declared, never inferred) — the linear
    # scorer's batch dim shards across the processes' devices, weights
    # replicate, and the out sharding is declared too.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    x_sh = NamedSharding(mesh, P("data", None))
    repl = NamedSharding(mesh, P())
    wrng = np.random.default_rng(7)
    W = wrng.normal(size=(6, 3)).astype(np.float32)
    b = wrng.normal(size=(3,)).astype(np.float32)
    local_X = GX[lo:hi].astype(np.float32)
    gX = jax.make_array_from_process_local_data(x_sh, local_X)

    score = jax.jit(lambda w, bias, x: x @ w + bias,
                    in_shardings=(repl, repl, x_sh),
                    out_shardings=x_sh)
    out = score(W, b, gX)
    mine = np.asarray(out.addressable_shards[0].data)
    expect = local_X @ W + b
    jit_ok = int(np.allclose(mine, expect, atol=1e-5))
    total = jax.jit(lambda x: jax.numpy.sum(x), in_shardings=x_sh,
                    out_shardings=repl)(out)
    print(f"SERVEJIT {pid} {jit_ok} {float(total):.3f}", flush=True)

    print(f"OK {pid}", flush=True)


if __name__ == "__main__":
    main()
