"""Driver of the cells that serve a ``hybrid_moe_lm`` as a scorer of
long rows: ``serve_model(json_scoring_pipeline(TPUModel))`` over HTTP, a
request one row of token ids, the reply the next token's id.

The window is made of ``drivers/serve.py``'s own steps (``start_client``,
``bring_up``, ``offer_window``) in ``serve.serve_window``'s order, as
``serve_lm.py``'s is, and the served classes are compared by its
``compare``. This file brings what the configuration brings: the plain
reference of ``reference_lfm2.py`` in ``forward``'s place, and the
model's per-row counters (``moe_tokens_held``,
``moe_load_max_over_mean``, ``moe_passes``) read from
``TPUModel.histograms()`` as the window closes.

**Near ties of the router.** Where an expert's score + bias nearly ties
the next one's, bfloat16 rightly chooses otherwise than float32 now and
then, and in this family one such choice among a row's last positions
moves its logits by a tenth: the short convolutions carry it to the last
position, two positions a layer (``serve_lm.py``'s rule, which looks at
the last position alone, would set every row aside). So the step hands
out the experts it chose at each row's last positions beside its logits
(``routed_tail``, the same execution), and the reference takes those in
its own choice's place at the positions that reach the last one by the
convolutions (``reference_lfm2.cone``) and nowhere else. Every choice it
takes over is held to its own, two ways: ``route_gap`` is the furthest
that a chosen expert's score + bias lies under the reference's k-th (a
rounding's worth at a near tie, far more for a choice by another rule),
and ``route_miss`` the share of the chosen experts that the reference
did not choose (a near tie now and then; a rule that errs by little but
always reads many). Earlier positions reach the last through attention
alone, one key among 8192, and the reference keeps its own choices
there.

**``attn_rel_l2``, ``attn_late_rel_l2``.** The last position's logits
hardly feel the q and k norms (with seeded weights q and k come out near
unit size anyway: the reference without them reads under the program's
own rounding), so the step also hands out every attention operator's
output at each row's last positions (``attention_tail``, the same
execution again) and the reference's is compared with it, a layer at a
time. The first attention layer lies before every expert layer, so its
number is the arithmetic alone; a later one's keys and values have been
through expert layers whose near ties, before the cone, the reference
decides for itself, and an attention output is an average in which such
moved values weigh what they weigh in the program's: its number (the
largest over the later layers) carries that, and has a limit of its own.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from drivers import serve, serve_lm

ROW_STATS = ("moe_tokens_held", "moe_load_max_over_mean", "moe_passes")
# what the sampled rows' step hands out beside their logits
TAILS = ("routed_tail", "attention_tail")


def serve_window(cell: dict, seed: int, window: float, trace_dir=None
                 ) -> dict:
    """``serve_lm.serve_window`` with this family's counters (PERF.md,
    Open questions 0j: ``serve.serve_window`` hands back neither the
    model nor its histograms, so its steps are repeated here)."""
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
    counters["weights_cast_leaves"] = model.metrics().get(
        "weights_cast_leaves")
    ok = [i for i, s in enumerate(result["status"]) if s == 200]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    rng = np.random.default_rng(loadgen.fold_seed(seed) + 2)
    sample = sorted(rng.choice(ok, size=min(tr["sample_requests"],
                                            len(ok)), replace=False)) \
        if ok else []
    misses = model.jit_cache_misses
    # the same compiled step, its choices named beside its logits
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


def tail_of(ref: dict, tail: int) -> np.ndarray:
    """(n, expert layers, tail, k): what a reference chose at each
    row's last positions, in ``routed_tail``'s layout."""
    return np.stack([ref["routed"][i][:, -tail:]
                     for i in sorted(ref["routed"])], axis=1)


def attention_layers(spec: dict) -> list:
    return [i for i, kind in enumerate(spec["layer_types"])
            if kind == "full_attention"]


def attended_by(ref: dict, tail: int) -> np.ndarray:
    """(n, attention layers, tail, d): a reference's attention outputs
    at each row's last positions, in ``attention_tail``'s layout (it
    kept those layers: ``keep_blocks``)."""
    return np.stack([ref["operators"][i][:, -tail:]
                     for i in sorted(ref["operators"])], axis=1)


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def attn_by_layer(attention_tail, ref: dict) -> list:
    """The relative distance of the attention outputs handed out from
    ``ref``'s at the same positions, an attention layer at a time."""
    theirs = attended_by(ref, attention_tail.shape[2])
    return [rel_l2(attention_tail[:, j], theirs[:, j])
            for j in range(theirs.shape[1])]


def compare(served, model_logits, attention_tail, forced_ref: dict,
            tr: dict, unanswered: int) -> list:
    """``serve.compare``'s checks against the reference that took the
    program's choices at the cone of each row's last positions;
    ``route_gap`` and ``route_miss``, the furthest any of those choices
    lies from the reference's own and the share of them it did not
    make; and ``attn_rel_l2`` and ``attn_late_rel_l2``, the attention
    outputs the step handed out against the reference's: the first
    attention layer's, and the largest of the later ones'."""
    limits = tr["limits"]
    checks = serve.compare(np.asarray(served), np.asarray(model_logits),
                           forced_ref["logits"], limits, unanswered)
    gaps = np.concatenate([g.ravel() for g in
                           forced_ref["route_gap"].values()])
    misses = np.concatenate([m.ravel() for m in
                             forced_ref["route_miss"].values()])
    k = next(iter(forced_ref["routed"].values())).shape[-1]
    by_layer = attn_by_layer(attention_tail, forced_ref)
    values = {
        "route_gap": float(gaps.max()) if gaps.size else 0.0,
        "route_miss": float(misses.sum() / (k * misses.size))
        if misses.size else 0.0,             # nothing forced: none missed
        "attn_rel_l2": by_layer[0]}
    if by_layer[1:]:
        values["attn_late_rel_l2"] = max(by_layer[1:])
    return checks + [{"name": name, "value": value, "limit": limits[name]}
                     for name, value in values.items()]


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import loadgen
    import reference_lfm2 as reference

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
        ref = reference.forward(got.pop("params"), got["rows"], spec,
                                keep_blocks=attention_layers(spec),
                                forced_tail=got["routed_tail"])
        checks = compare(got["served"], got["model_logits"],
                         got["attention_tail"], ref, tr, got["unanswered"])
        # row by row, for the record
        rows_info = {
            "rows_rel_l2": serve_lm.row_rel_l2(
                got["model_logits"], ref["logits"]).tolist(),
            "rows_route_gap": np.max(
                [g.max(axis=1) for g in ref["route_gap"].values()],
                axis=0).tolist(),
            "attn_rel_l2_by_layer": attn_by_layer(got["attention_tail"],
                                                  ref)}
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
                     "device_wait_ms", "rows_scored",
                     "weights_cast_leaves", *ROW_STATS)},
                 **rows_info},
    }


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed after a short window at
    the cell's own load: 'sound' is the program; every other name is a
    stand-in of ``control_lfm2.STAND_INS``, the reference with one
    thing changed, in the program's place at the same rows (it need not
    serve: the token it puts first is its answer, and what it chose at
    each row's last positions its ``routed_tail``). 'unforced' is the
    program against the reference left to its own choices: what near
    ties cost where nothing sets them aside, for the record."""
    import control_lfm2
    import reference_lfm2 as reference
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    got = serve_window(cell, seed, cell["seconds"])
    gc.collect()
    params, rows = got.pop("params"), got["rows"]
    tail, layers = got["routed_tail"].shape[2], attention_layers(spec)
    out, info = {}, {"classes_in_sample": len(set(got["served"]))}
    for name in which:
        if name in ("sound", "unforced"):
            served, logits, routed, attn = (
                got["served"], got["model_logits"], got["routed_tail"],
                got["attention_tail"])
        else:
            stand_in = reference.forward(
                params, rows, spec, keep_blocks=layers,
                **control_lfm2.STAND_INS[name])
            logits, routed = stand_in["logits"], tail_of(stand_in, tail)
            served = logits.argmax(-1)
            attn = attended_by(stand_in, got["attention_tail"].shape[2])
            del stand_in
        ref = reference.forward(
            params, rows, spec, keep_blocks=layers,
            forced_tail=None if name == "unforced" else routed)
        if name == "unforced":
            own = tail_of(ref, tail)
            info["tail_miss_unforced"] = float(1.0 - (
                routed[..., :, None] == own[..., None, :]).any(-1).mean())
        out[name] = compare(served, logits, attn, ref, tr,
                            got["unanswered"])
        info[f"rows_rel_l2_{name}"] = serve_lm.row_rel_l2(
            logits, ref["logits"]).tolist()
        info[f"attn_rel_l2_by_layer_{name}"] = attn_by_layer(attn, ref)
        if name == "sound":
            top2 = np.sort(ref["logits"], axis=-1)[:, -2:]
            info.update(
                reference_top2_margin_min=float(
                    (top2[:, 1] - top2[:, 0]).min()),
                reference_spread_over_classes=float(
                    ref["logits"].std(axis=1).mean()))
    out["info"] = info
    return out
