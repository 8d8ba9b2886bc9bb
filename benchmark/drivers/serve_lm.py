"""Driver of the cells that serve a language model as a scorer of long
rows: ``serve_model(json_scoring_pipeline(TPUModel))`` over HTTP, a
request one row of token ids, the reply the next token's id.

The window is made of ``drivers/serve.py``'s own steps (``start_client``,
``bring_up``, ``offer_window``) in ``serve.serve_window``'s order, and
the served classes are compared by its ``compare``; this file adds what
a ``latent_moe_lm`` brings: the plain reference of
``reference_glm_dsa.py`` in ``forward``'s place, the model's per-row
counters read from ``TPUModel.histograms()`` as the window closes, and
``select_miss``: the share of the keys that the program selected for
the sampled rows, in the last layer that has a selector, which the
reference did not select for the same row. The program returns its sets
through ``capture``, in a step of its own after the window. A sampled
row whose last position is a near tie of the router for an expert held
here is set aside (``near_tie_rows``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from drivers import serve

ROW_STATS = ("moe_tokens_held", "moe_load_max_over_mean",
             "dsa_keys_per_query")


def serve_window(cell: dict, seed: int, window: float, trace_dir=None
                 ) -> dict:
    """What ``serve.serve_window`` does and hands back, step for step,
    with the model's per-row counters of the window among its counters
    (the mean over the window's rows of each, and how many rows were
    scored): they are read as the window closes, before the sampled
    rows pass through the same TPUModel and count as well, and
    ``serve.serve_window`` hands back neither the model nor its
    histograms (PERF.md, Open questions 0j)."""
    import jax
    from mmlspark_tpu.core.table import DataTable

    import loadgen
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    seq, vocab = spec["max_len"], spec["vocab_size"]
    client = serve.start_client(cell, seed, window, spec)
    engine = None
    try:
        variables, model, engine = serve.bring_up(cell, seed)
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
    hists = model.histograms()
    for name in ROW_STATS:
        counters[name] = serve.hist_mean(hists[name])
    counters["rows_scored"] = hists[ROW_STATS[0]].snapshot()["count"]
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
        if sample else np.zeros((0, vocab))
    counters["recompiles"] += model.jit_cache_misses - misses
    counters.update(rows_ok=len(ok), seq=seq, bucket=tr["batch_size"])
    return {"result": result, "counters": counters, "peak": peak,
            "attempted": len(due), "unanswered": len(due) - len(ok),
            "rows": rows[sample], "model_logits": model_logits,
            "served": [int(result["answer"][i]["prediction"])
                       for i in sample],
            "params": variables["params"]}


def program_capture(cell: dict, params, rows, name: str):
    """What the program's ``capture=name`` gives for ``rows`` (the
    (n, l, l) tables of selected keys for ``selected_<layer>``), a
    bucket of rows at a time."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    spec = cell["config_file"]["networkSpec"]
    module = build_network({"dtype": "bfloat16", **spec})
    step = cell["traffic_file"]["batch_size"]

    @jax.jit
    def captured(p, tokens):
        return module.apply({"params": p}, tokens, capture=name)
    out = []
    for i in range(0, len(rows), step):
        chunk = np.asarray(rows[i:i + step])
        pad = step - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, chunk[-1:].repeat(pad, 0)])
        got = np.asarray(captured(params, jnp.asarray(chunk, jnp.int32)))
        out.append(got[:step - pad])
    return np.concatenate(out)


def last_selector_layer(spec: dict) -> int:
    return max(i for i, kind in enumerate(spec["indexer_types"])
               if kind == "full")


def select_miss(program, reference) -> float:
    """Share of the program's selected keys that are not in the
    reference's set of the same row and query."""
    program, reference = np.asarray(program), np.asarray(reference)
    return float((program & ~reference).sum() / max(1, program.sum()))


def near_tie_rows(ref: dict, margin: float) -> np.ndarray:
    """(n,) bool: the rows at whose last position, in some expert
    layer of the reference, score + bias would have to move by less
    than ``margin`` for an expert held here to enter or leave the
    chosen set (``reference_glm_dsa.experts``). There the stated
    precision may rightly choose otherwise, which moves the row's
    logits by a tenth: such a row says nothing about the program and
    is set aside."""
    return last_margins(ref).min(axis=1) < margin


def last_margins(ref: dict) -> np.ndarray:
    """(n, expert layers): the reference's ``router_margin`` at each
    row's last position."""
    return np.stack([m[:, -1] for m in ref["router_margin"].values()],
                    axis=1)


def row_rel_l2(model_logits, ref_logits) -> np.ndarray:
    """``logit_rel_l2`` row by row."""
    model_logits = np.asarray(model_logits, np.float64)
    ref_logits = np.asarray(ref_logits, np.float64)
    return np.linalg.norm(model_logits - ref_logits, axis=-1) \
        / np.linalg.norm(ref_logits, axis=-1)


def compare(served, model_logits, sets, ref: dict, layer: int, tr: dict,
            unanswered: int) -> list:
    """``serve.compare``'s checks, with ``class_gap`` and
    ``logit_rel_l2`` read over the rows that are no near tie (above),
    how many rows were set aside, and ``select_miss``."""
    limits = tr["limits"]
    served, model_logits = np.asarray(served), np.asarray(model_logits)
    aside = near_tie_rows(ref, tr["near_tie_margin"])
    checks = {c["name"]: c for c in serve.compare(
        served, model_logits, ref["logits"], limits, unanswered)}
    if not aside.all():
        checks.update({c["name"]: c for c in serve.compare(
            served[~aside], model_logits[~aside], ref["logits"][~aside],
            limits, unanswered) if c["name"] in ("class_gap",
                                                 "logit_rel_l2")})
    checks["near_tie_rows"] = {"name": "near_tie_rows",
                               "value": int(aside.sum()),
                               "limit": limits["near_tie_rows"]}
    checks["select_miss"] = {
        "name": "select_miss", "limit": limits["select_miss"],
        "value": select_miss(sets, ref["selected"][layer])}
    return list(checks.values())


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import loadgen
    import reference_glm_dsa as reference

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
        params, layer = got.pop("params"), last_selector_layer(spec)
        sets = program_capture(cell, params, got["rows"],
                               f"selected_{layer}")
        ref = reference.forward(params, got["rows"], spec)
        checks = compare(got["served"], got["model_logits"], sets, ref,
                         layer, tr, got["unanswered"])
        # row by row, for the record: a row set aside that reads a
        # tenth flipped an expert held here, one that reads a hundredth
        # did not
        rows_info = {
            "rows_rel_l2": row_rel_l2(got["model_logits"],
                                      ref["logits"]).tolist(),
            "rows_margin_min": last_margins(ref).min(axis=1).tolist()}
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
                     "device_wait_ms", "rows_scored", *ROW_STATS)},
                 **rows_info},
    }


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed after a short window at
    the cell's own load: 'sound' is the program; every other name is a
    stand-in of ``control_glm_dsa.STAND_INS``, the reference with one
    thing changed, in the program's place at the same rows (it need not
    serve: the token it puts first is its answer)."""
    import control_glm_dsa
    import reference_glm_dsa as reference
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    got = serve_window(cell, seed, cell["seconds"])
    gc.collect()
    params, rows = got.pop("params"), got["rows"]
    layer = last_selector_layer(spec)
    ref = reference.forward(params, rows, spec)
    out = {}
    for name in which:
        if name == "sound":
            served, logits = got["served"], got["model_logits"]
            sets = program_capture(cell, params, rows,
                                   f"selected_{layer}")
        else:
            stand_in = reference.forward(
                params, rows, spec, **control_glm_dsa.STAND_INS[name])
            logits = stand_in["logits"]
            served, sets = logits.argmax(-1), stand_in["selected"][layer]
        out[name] = compare(served, logits, sets, ref, layer, tr,
                            got["unanswered"])
    top2 = np.sort(ref["logits"], axis=-1)[:, -2:]
    out["info"] = {"classes_in_sample": len(set(got["served"])),
                   "reference_top2_margin_min": float(
                       (top2[:, 1] - top2[:, 0]).min()),
                   "reference_spread_over_classes": float(
                       ref["logits"].std(axis=1).mean())}
    return out
