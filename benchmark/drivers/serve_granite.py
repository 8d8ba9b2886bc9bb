"""Driver of the cell that serves the ``granite-4.0-h-micro``
configuration, a ``hybrid_moe_lm`` whose layers are Mamba-2 state-space
layers with a position-free attention layer every ten, a dense
feed-forward in every layer and no expert, as a scorer of rows of about
a thousand tokens: ``serve_model(json_scoring_pipeline(TPUModel))`` over
HTTP, a request one row of token ids, the reply the next token's id.

The window is made of ``drivers/serve.py``'s own steps (``start_client``,
``bring_up``, ``offer_window``) in ``serve.serve_window``'s order, as
``serve_hybrid_lm.serve_window`` is, with the two tails this family's
step hands out for the sampled rows: ``ssm_tail`` and
``attention_tail``. Its ``bring_up`` draws the weights by an ``init``
that keeps the parameters alone (below). There is no router, so nothing of the program's is
handed to the reference, which is left to its own arithmetic
(``reference_granite.py``). The comparison is ``serve.compare``'s checks
and two operators out of the served step against the reference's:
``ssm_rel_l2``, the first Mamba-2 layer's output at each row's last
positions (layer 0: it lies before every other layer, so it is the
conv's, the scan's, the carried state's and the gated norm's arithmetic
alone), and ``attn_rel_l2``, the first attention layer's (layer 5: the
scale folded into q, no q/k norm, no rotary step). The stand-ins of
``control_granite.py`` are the reference with one thing changed.
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from drivers import serve, serve_hybrid_lm, serve_lm

# what the sampled rows' step hands out beside their logits
TAILS = ("ssm_tail", "attention_tail")
rel_l2 = serve_hybrid_lm.rel_l2


def init_lean(module):
    """``jax.jit(module.init)`` keeping the collection ``params`` alone.
    The forward that ``init`` runs sows the row stats; where they are
    kept they keep every layer at (1, ``max_len``) live in the compiled
    draw. Left out, XLA drops the forward and compiles the draws only;
    the parameters are the same."""
    import jax
    return jax.jit(lambda key, x: module.init(key, x, mutable=["params"]))


def bring_up(cell: dict, seed: int):
    """``serve.bring_up``, with the weights drawn by ``init_lean``."""
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
    variables = init_lean(module)(
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


def serve_window(cell: dict, seed: int, window: float, trace_dir=None
                 ) -> dict:
    """``serve.serve_window`` with the step's two tails for the sampled
    rows (``serve.serve_window`` hands back neither the model nor its
    outputs by name, so its steps are repeated here)."""
    import jax
    from mmlspark_tpu.core.table import DataTable

    import loadgen
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    seq, vocab = spec["max_len"], spec["vocab_size"]
    client = serve.start_client(cell, seed, window, spec)
    engine = None
    try:
        variables, model, engine = bring_up(cell, seed)
        due = loadgen.schedule(tr["arrivals"], window)
        rows = loadgen.token_rows(seed, len(due), seq, vocab)
        result, counters = serve.offer_window(client, engine, model,
                                              trace_dir)
    finally:
        if engine is not None:
            engine.stop()
        if client.poll() is None:
            client.kill()
            client.wait()
    metrics = model.metrics()
    for name in ("ssm_layers", "ssm_chunks", "ssm_state_bytes",
                 "weights_cast_leaves"):
        counters[name] = metrics.get(name)
    ok = [i for i, s in enumerate(result["status"]) if s == 200]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    rng = np.random.default_rng(loadgen.fold_seed(seed) + 2)
    sample = sorted(rng.choice(ok, size=min(tr["sample_requests"],
                                            len(ok)), replace=False)) \
        if ok else []
    misses = model.jit_cache_misses
    # the same compiled step, its operators' tails named beside its logits
    model.set("fetchDict", {"scores": "output", **{n: n for n in TAILS}})
    scored = model.transform(DataTable(
        {"features": rows[sample].astype(np.float32)})) if sample else None
    model_logits = np.asarray(scored["scores"]) if sample \
        else np.zeros((0, vocab))
    counters["recompiles"] += model.jit_cache_misses - misses
    counters.update(rows_ok=len(ok), seq=seq, bucket=tr["batch_size"])
    return {"result": result, "counters": counters, "peak": peak,
            "attempted": len(due), "unanswered": len(due) - len(ok),
            "rows": rows[sample], "model_logits": model_logits,
            **{n: np.asarray(scored[n]) if sample else None
               for n in TAILS},
            "served": [int(result["answer"][i]["prediction"])
                       for i in sample],
            "params": variables["params"]}


def held_layers(spec: dict) -> dict:
    """{check: layer} of the two operator outputs that are compared:
    the first layer of each kind."""
    kinds = list(spec["layer_types"])
    return {"ssm_rel_l2": kinds.index("mamba"),
            "attn_rel_l2": kinds.index("full_attention")}


def tails_of(ref: dict, spec: dict) -> dict:
    """A reference's kept operators in the layout of the step's tails:
    {tail name: (n, 1, positions, d)}, the first layer of each kind."""
    held = held_layers(spec)
    return {"ssm_tail": ref["operators"][held["ssm_rel_l2"]][:, None],
            "attention_tail": ref["operators"][held["attn_rel_l2"]][:, None]}


def compare(served, model_logits, tails: dict, ref: dict, spec: dict,
            tr: dict, unanswered: int) -> list:
    """``serve.compare``'s checks against the reference, and the first
    Mamba-2 and the first attention operator's outputs handed out
    (each kind's first entry of its tail) against the reference's, at
    the positions it kept."""
    limits = tr["limits"]
    checks = serve.compare(np.asarray(served), np.asarray(model_logits),
                           ref["logits"], limits, unanswered)
    held = held_layers(spec)
    values = {}
    for name, tail in (("ssm_rel_l2", "ssm_tail"),
                       ("attn_rel_l2", "attention_tail")):
        theirs = ref["operators"][held[name]]
        values[name] = rel_l2(tails[tail][:, 0, -theirs.shape[1]:], theirs)
    return checks + [{"name": name, "value": value, "limit": limits[name]}
                     for name, value in values.items()]


def reference_of(params, rows, spec, tail: int, **controls) -> dict:
    """The reference over ``rows``, keeping the two compared layers'
    operators at each row's last ``tail`` positions."""
    import reference_granite as reference
    return reference.forward(
        params, rows, spec, keep_blocks=sorted(held_layers(spec).values()),
        keep_tail=tail, **controls)


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import loadgen

    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    window = min(seconds, tr["trace_window_s"]) if trace_dir else seconds
    got = serve_window(cell, seed, window, trace_dir)
    result, counters = got["result"], got["counters"]
    lat = loadgen.latencies_ms(result, tr["reply_timeout_s"] * 2e3)

    # the program's state is gone before the reference takes the chip
    gc.collect()
    t_ref = time.time()
    rows_info = {}
    if got["served"]:
        ref = reference_of(got.pop("params"), got["rows"], spec,
                           got["ssm_tail"].shape[2])
        checks = compare(got["served"], got["model_logits"],
                         {n: got[n] for n in TAILS}, ref, spec, tr,
                         got["unanswered"])
        # row by row, for the record
        top2 = np.sort(ref["logits"], axis=-1)[:, -2:]
        rows_info = {
            "rows_rel_l2": serve_lm.row_rel_l2(
                got["model_logits"], ref["logits"]).tolist(),
            "reference_top2_margin_min": float(
                (top2[:, 1] - top2[:, 0]).min())}
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
                 **{k: counters.get(k) for k in (
                     "late_ms_p50", "late_ms_max", "batch_rows",
                     "batches", "recompiles", "queue_wait_ms",
                     "device_wait_ms", "weights_cast_leaves", "ssm_layers",
                     "ssm_chunks", "ssm_state_bytes")},
                 **rows_info},
    }


# sampled rows a stand-in is computed over (each costs two passes of the
# reference, its own and the one it is compared with, and there are 16
# stand-ins); the program's own numbers ('sound') are over every sampled
# row
CONTROL_ROWS = 2


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed after a short window at
    the cell's own load: 'sound' is the program; every other name is a
    stand-in of ``control_granite.STAND_INS``, the reference with one
    thing changed, in the program's place at the first ``CONTROL_ROWS``
    of the same rows (it need not serve: the token it puts first is its
    answer, and its kept operators its tails)."""
    import control_granite
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    got = serve_window(cell, seed, cell["seconds"])
    gc.collect()
    params, rows = got.pop("params"), got["rows"]
    tail = got["ssm_tail"].shape[2]
    out, info = {}, {"classes_in_sample": len(set(got["served"]))}
    refs = {}           # the sound reference over n rows, made once

    def reference_over(n):
        if n not in refs:
            refs[n] = reference_of(params, rows[:n], spec, tail)
        return refs[n]
    for name in which:
        if name == "sound":
            over, served, logits = rows, got["served"], got["model_logits"]
            tails = {n: got[n] for n in TAILS}
        else:
            over = rows[:CONTROL_ROWS]
            stand_in = reference_of(params, over, spec, tail,
                                    **control_granite.STAND_INS[name])
            logits, tails = stand_in["logits"], tails_of(stand_in, spec)
            served = logits.argmax(-1)
            del stand_in
        ref = reference_over(len(over))
        out[name] = compare(served, logits, tails, ref, spec, tr,
                            got["unanswered"])
        info[f"rows_rel_l2_{name}"] = serve_lm.row_rel_l2(
            logits, ref["logits"]).tolist()
        if name == "sound":
            top2 = np.sort(ref["logits"], axis=-1)[:, -2:]
            info.update(
                reference_top2_margin_min=float(
                    (top2[:, 1] - top2[:, 0]).min()),
                reference_spread_over_classes=float(
                    ref["logits"].std(axis=1).mean()))
    out["info"] = info
    return out
