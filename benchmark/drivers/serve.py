"""Driver of the serving cells: ``serve_model(json_scoring_pipeline(
TPUModel))`` over HTTP in this process, which holds the chip, and the
load from ``loadgen.py`` in a process of its own.

Set-up builds the weights on the device in one jitted call from the
seed, warms the one bucket, starts the engine and sends a few requests
through it; the client meanwhile prepares its bodies. The window opens
when the first request is due. After it closes every reply is waited
for. ``correct`` then takes a sample of the answered requests, drawn
from the seed, and runs the plain reference over each: the widest gap
by which a served class's logit lies below the reference's best; how
many served classes are not the first class of the logits that the same
TPUModel and the same compiled bucket give for that row; and those
logits against the reference's.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np


def start_client(cell: dict, seed: int, seconds: float, spec: dict,
                 traffic_path: str = ""):
    here = cell["home"]
    return subprocess.Popen(
        [sys.executable, os.path.join(here, "loadgen.py"),
         "--traffic", traffic_path or os.path.join(
             here, "traffic", cell["traffic"] + ".json"),
         "--seed", str(seed), "--seconds", str(seconds),
         "--seq", str(spec["max_len"]), "--vocab", str(spec["vocab_size"])],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items()
             if not k.startswith(("JAX_", "XLA_", "TPU_"))})


def bring_up(cell: dict, seed: int):
    """Weights on the device in one jitted call from the seed, the one
    bucket warmed, the engine started and a few requests through it."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline
    from mmlspark_tpu.serving.server import serve_model

    import loadgen
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    seq = spec["max_len"]
    module = build_network({"dtype": "bfloat16", **spec})
    variables = jax.jit(module.init)(
        jax.random.PRNGKey(loadgen.fold_seed(seed)),
        jnp.zeros((1, seq), jnp.int32))
    model = TPUModel.from_flax(module, variables, inputCol="features",
                               outputCol="scores",
                               batchSize=tr["batch_size"])
    warm_rows = loadgen.token_rows(seed, tr["warm_requests"], seq,
                                   spec["vocab_size"])
    model.warmup({"features": warm_rows[:1].astype(np.float32)})
    engine = serve_model(
        json_scoring_pipeline(model, field="features"), port=0,
        batch_size=tr["batch_size"], max_wait_ms=tr["max_wait_ms"],
        workers=tr["workers"])
    warm = [json.dumps({"features": r.tolist()}).encode()
            for r in warm_rows]
    loadgen.offer(engine.source.address, warm, np.zeros(len(warm)),
                  len(warm), 60)
    return variables, model, engine


def offer_window(client, engine, model, trace_dir=None) -> tuple:
    """Open the window: reset the counters, tell the client to go, wait
    for its one line of results."""
    import jax
    misses = model.jit_cache_misses
    for h in list(engine.hists.values()) + list(
            model.histograms().values()):
        h.reset()
    assert client.stdout.readline().strip() == "ready"
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        client.stdin.write(json.dumps(
            {"address": engine.source.address}) + "\n")
        client.stdin.flush()
        result = json.loads(client.stdout.readline())
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    client.wait(timeout=30)
    counters = {
        "queue_wait_ms": hist_mean(engine.hists["queue_wait_ms"]),
        "batch_rows": hist_mean(engine.hists["batch_rows"]),
        "batches": engine.hists["batch_rows"].snapshot()["count"],
        "device_wait_ms": hist_mean(model.histograms()["device_ms"]),
        "pad_ms": hist_mean(model.histograms()["pad_ms"]),
        "recompiles": model.jit_cache_misses - misses,
        "late_ms_p50": result["late_ms_p50"],
        "late_ms_max": result["late_ms_max"],
    }
    return result, counters


def hist_mean(hist) -> float | None:
    snap = hist.snapshot()
    return snap["sum"] / snap["count"] if snap["count"] else None


def compare(served, model_logits, ref_logits, limits: dict,
            unanswered: int) -> list:
    """``served`` are the classes the window's replies carried for the
    sampled requests; the logits are (n, classes)."""
    served = np.asarray(served)
    ref_logits = np.asarray(ref_logits, np.float64)
    model_logits = np.asarray(model_logits, np.float64)
    best = ref_logits.max(axis=-1)
    gaps = best - ref_logits[np.arange(len(served)), served]
    rel = float(np.linalg.norm(model_logits - ref_logits)
                / np.linalg.norm(ref_logits))
    values = {"class_gap": float(gaps.max()), "logit_rel_l2": rel,
              "served_not_model": int(
                  (model_logits.argmax(-1) != served).sum()),
              "unanswered": unanswered}
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in values.items()]


def serve_window(cell: dict, seed: int, window: float, trace_dir=None
                 ) -> dict:
    """Bring the model up, offer the window's load, wait for every
    reply, and ask the same TPUModel for the logits of a sample of the
    answered requests, drawn from the seed."""
    import jax
    from mmlspark_tpu.core.table import DataTable

    import loadgen
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    seq, vocab = spec["max_len"], spec["vocab_size"]
    # the client prepares its bodies while the model is brought up
    client = start_client(cell, seed, window, spec)
    engine = None
    try:
        variables, model, engine = bring_up(cell, seed)
        due = loadgen.schedule(tr["arrivals"], window)
        rows = loadgen.token_rows(seed, len(due), seq, vocab)
        result, counters = offer_window(client, engine, model, trace_dir)
    finally:
        if engine is not None:
            engine.stop()
        if client.poll() is None:
            client.kill()
            client.wait()
    ok = [i for i, s in enumerate(result["status"]) if s == 200]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    rng = np.random.default_rng(loadgen.fold_seed(seed) + 2)
    sample = sorted(rng.choice(ok, size=min(tr["sample_requests"],
                                            len(ok)), replace=False)) \
        if ok else []
    misses = model.jit_cache_misses
    model_logits = np.asarray(model.transform(DataTable(
        {"features": rows[sample].astype(np.float32)}))["scores"]) \
        if sample else np.zeros((0, spec["num_classes"]))
    counters["recompiles"] += model.jit_cache_misses - misses
    counters.update(rows_ok=len(ok), seq=seq, bucket=tr["batch_size"])
    return {"result": result, "counters": counters, "peak": peak,
            "attempted": len(due), "unanswered": len(due) - len(ok),
            "rows": rows[sample], "model_logits": model_logits,
            "served": [int(result["answer"][i]["prediction"])
                       for i in sample],
            "params": variables["params"]}


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import loadgen
    import reference

    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    window = min(seconds, tr["trace_window_s"]) if trace_dir else seconds
    got = serve_window(cell, seed, window, trace_dir)
    result, counters = got["result"], got["counters"]
    lat = loadgen.latencies_ms(result, tr["reply_timeout_s"] * 2e3)

    # the program's state is gone before the reference takes the chip
    gc.collect()
    t_ref = time.time()
    if got["served"]:
        ref_logits = reference.forward(got.pop("params"), got["rows"],
                                       spec["heads"])
        checks = compare(got["served"], got["model_logits"], ref_logits,
                         tr["limits"], got["unanswered"])
    else:
        checks = [{"name": "unanswered", "value": got["unanswered"],
                   "limit": 0}]
    return {
        "end_to_end": {"serve_p50_ms": loadgen.percentile(lat, 50),
                       "serve_p95_ms": loadgen.percentile(lat, 95),
                       "setup_s": result["started_epoch"] - t_start},
        "attempted": got["attempted"], "failed": got["unanswered"],
        "checks": checks, "memory_peak_bytes": got["peak"],
        "trace_skip_first": 0, "counters": counters,
        "info": {"window_s": window, "serve_mean_ms": sum(lat) / len(lat),
                 "reference_s": time.time() - t_ref,
                 "sampled": len(got["served"]),
                 "classes_in_sample": len(set(got["served"])),
                 **{k: counters[k] for k in (
                     "late_ms_p50", "late_ms_max", "batch_rows",
                     "batches", "recompiles", "queue_wait_ms",
                     "device_wait_ms")}},
    }


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed after a short window at
    the cell's own load: 'sound' is the program; 'int8' and 'fp8' the
    reference with its matmuls in the precision below bfloat16 in the
    program's place, at the same rows (it need not serve: the class it
    puts first is its answer); 'swapped' the program's replies handed
    each to the next sampled request; 'altered' every class one on."""
    import reference
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    got = serve_window(cell, seed, cell["seconds"])
    gc.collect()
    params, rows = got.pop("params"), got["rows"]
    served, logits = got["served"], got["model_logits"]
    ref = reference.forward(params, rows, spec["heads"])
    def low(precision):
        lg = reference.forward(params, rows, spec["heads"], precision)
        return lg.argmax(-1), lg
    stand_ins = {
        "sound": lambda: (served, logits),
        "int8": lambda: low("int8"), "fp8": lambda: low("fp8"),
        "swapped": lambda: (np.roll(served, 1), logits),
        "altered": lambda: ((np.asarray(served) + 1) % ref.shape[-1],
                            np.roll(logits, 1, -1)),
    }
    out = {name: compare(*stand_ins[name](), ref, tr["limits"],
                         got["unanswered"]) for name in which}
    top2 = np.sort(ref, axis=-1)[:, -2:]
    out["info"] = {"classes_in_sample": len(set(served)),
                   "reference_top2_margin_min": float(
                       (top2[:, 1] - top2[:, 0]).min()),
                   "reference_spread_over_rows": float(
                       ref.std(axis=0).mean()),
                   "reference_spread_over_classes": float(
                       ref.std(axis=1).mean())}
    return out
