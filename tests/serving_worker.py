"""One serving-host process for the cross-process serving tests.

The reference's serving is genuinely per-executor — one JVMSharedServer
in every executor process with reply-by-uuid routing
(ref: src/io/http/src/main/scala/DistributedHTTPSource.scala:96-266).
This worker is the TPU-native equivalent of one executor: its own OS
process, its own ServingEngine + port, its own counters. The parent
(tests/test_distributed.py, tests/test_sharded.py) sprays requests across all workers and checks the
reply-routing invariant and the fleet-wide counter aggregate.

Two scorers:

- ``echo`` (default — the original contract, kept verbatim for
  test_distributed): JSON bodies ``{"x": ...}`` echo back with the
  worker id; ``{"__shutdown__": true}`` stops the worker and prints its
  counters.
- ``linear``: a real model behind the engine hot path — a
  deterministic (seeded) linear ``TPUModel`` served through
  ``json_scoring_pipeline``, so the worker speaks BOTH the JSON oracle
  and the columnar ingress protocol (msgpack-columns / Arrow) and
  every worker in a fleet computes identical predictions. The
  multi-process fleet bench's load generator drives this with
  ``fleet.post_columns``. Runs until killed (the chaos drill SIGKILLs
  it mid-load).

``--start-delay`` sleeps BEFORE binding the port — the slow-starting
worker shape the ``ServingFleet.connect`` startup probe exists for.

Usage: python serving_worker.py <port> <worker_id>
           [--scorer echo|linear] [--dim D] [--classes K]
           [--batch-size B] [--workers W] [--start-delay S]
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build_linear_stage(dim: int, classes: int, batch_size: int):
    """The deterministic linear scorer every worker agrees on: weights
    from a FIXED seed, served through json_scoring_pipeline — the full
    engine hot path incl. columnar ingress, buckets, and warmup."""
    import numpy as np
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline

    rng = np.random.default_rng(7)
    weights = {"W": rng.normal(size=(dim, classes)).astype(np.float32),
               "b": rng.normal(size=(classes,)).astype(np.float32)}

    def fwd(w, inputs):
        x = list(inputs.values())[0]
        return {"output": x @ w["W"] + w["b"]}

    model = TPUModel.from_fn(fwd, weights, inputCol="features",
                             outputCol="scores",
                             batchSize=batch_size)
    stage = json_scoring_pipeline(model, field="features")
    example = {"features": rng.normal(size=(2, dim)).astype(np.float32)}
    stage.warmup(example)
    return stage


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("port", type=int)
    ap.add_argument("worker_id", type=int)
    ap.add_argument("--scorer", choices=["echo", "linear"],
                    default="echo")
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--start-delay", type=float, default=0.0)
    args = ap.parse_args()
    port, wid = args.port, args.worker_id

    if args.start_delay > 0:
        # simulate the slow-starting replica (import + model build
        # before the port binds) deterministically
        time.sleep(args.start_delay)

    from mmlspark_tpu.serving.server import HTTPSource, ServingEngine
    from mmlspark_tpu.stages.basic import Lambda

    stop = threading.Event()

    if args.scorer == "linear":
        stage = _build_linear_stage(args.dim, args.classes,
                                    args.batch_size)
        source = HTTPSource(host="127.0.0.1", port=port)
        engine = ServingEngine(source, stage,
                               batch_size=args.batch_size,
                               workers=args.workers,
                               slo=False,
                               flight_recorder=False).start()
        print(f"READY {wid} {source.address} {os.getpid()}", flush=True)
        try:
            stop.wait()          # runs until killed (chaos SIGKILLs)
        finally:
            engine.stop()
        return

    def handle(table):
        replies = []
        for r in table["request"]:
            body = json.loads(r["entity"].decode())
            if body.get("__shutdown__"):
                stop.set()
                replies.append({"bye": wid})
            else:
                # replies carry the worker identity so the test can
                # assert each answer returned through the SAME process
                # that accepted it
                replies.append({"echo": body["x"], "worker": wid})
        return table.with_column("reply", replies)

    source = HTTPSource(host="127.0.0.1", port=port)
    engine = ServingEngine(source, Lambda.apply(handle),
                           batch_size=8).start()
    print(f"READY {wid} {source.address}", flush=True)

    stop.wait(timeout=120)
    time.sleep(0.3)   # let the shutdown reply flush
    print(f"COUNTERS {wid} {source.requests_seen} "
          f"{source.requests_accepted} {source.requests_answered}",
          flush=True)
    engine.stop()


if __name__ == "__main__":
    main()
