"""Driver of the cell that serves the ``trinity-mini-stage``
configuration, a ``hybrid_moe_lm`` whose layers are all attention with
an output gate (sliding-window layers that turn by a rotary table and
full ones that carry no positions, 3 to 1), four norms a layer, a dense
feed-forward after the first and after the others sigmoid-routed
experts with a shared one beside them, as a scorer of long rows:
``serve_model(json_scoring_pipeline(TPUModel))`` over HTTP, a request
one row of token ids, the reply the next token's id.

The window is ``serve_hybrid_lm.serve_window`` (``drivers/serve.py``'s
own steps, with this family's per-row counters and the two tails the
step hands out for the sampled rows) and the comparison is
``serve_mellum2.compare``, as that cell's: ``serve.compare``'s checks
against the reference that took the experts the served step chose at
each row's last position (``routed_tail``; no operator here carries a
choice sideways but attention), ``route_gap`` and ``route_miss`` over
every choice taken over, and the first sliding and the first full
layer's attention outputs out of the served step (``attention_tail``)
against the reference's: ``swa_rel_l2`` (cut layer 0: it lies before
every expert layer, so it is the window's, the rotary table's and the
gate's arithmetic alone) and ``full_rel_l2`` (cut layer 2: no table, the
gate). This file brings what the configuration brings: the plain
reference of ``reference_trinity.py`` and the stand-ins of
``control_trinity.py``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from drivers import serve_hybrid_lm, serve_lm, serve_mellum2

ROW_STATS = serve_hybrid_lm.ROW_STATS
serve_window = serve_hybrid_lm.serve_window
tail_of = serve_hybrid_lm.tail_of
held_layers = serve_mellum2.held_layers
last_choice = serve_mellum2.last_choice
compare = serve_mellum2.compare


def reference_of(params, rows, spec, forced, tail: int, **controls
                 ) -> dict:
    """The reference over ``rows``, keeping the two compared layers'
    operators at each row's last ``tail`` positions
    (``attention_tail``'s)."""
    import reference_trinity as reference
    return reference.forward(
        params, rows, spec, keep_blocks=sorted(held_layers(spec).values()),
        keep_tail=tail, forced_tail=forced, **controls)


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
                           last_choice(got["routed_tail"]),
                           got["attention_tail"].shape[2])
        checks = compare(got["served"], got["model_logits"],
                         got["attention_tail"], ref, spec, tr,
                         got["unanswered"])
        # row by row, for the record
        rows_info = {
            "rows_rel_l2": serve_lm.row_rel_l2(
                got["model_logits"], ref["logits"]).tolist(),
            "rows_route_gap": np.max(
                [g.max(axis=1) for g in ref["route_gap"].values()],
                axis=0).tolist(),
            "router_margin_last_min": float(min(
                m[:, -1].min() for m in ref["router_margin"].values()))}
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


# sampled rows a stand-in is computed over (each costs two passes of the
# reference, its own and the one it is compared with, and there are 16
# stand-ins); the program's own numbers ('sound', 'unforced') are over
# every sampled row
CONTROL_ROWS = 2


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed after a short window at
    the cell's own load: 'sound' is the program; every other name is a
    stand-in of ``control_trinity.STAND_INS``, the reference with one
    thing changed, in the program's place at the first ``CONTROL_ROWS``
    of the same rows (it need not serve: the token it puts first is its
    answer, what it chose at each row's last position its
    ``routed_tail``, and its kept attention outputs its
    ``attention_tail``). 'unforced' is the program against the
    reference left to its own choices, for the record."""
    import control_trinity
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    got = serve_window(cell, seed, cell["seconds"])
    gc.collect()
    params, rows = got.pop("params"), got["rows"]
    layers, tail = len(spec["layer_types"]), got["attention_tail"].shape[2]
    out, info = {}, {"classes_in_sample": len(set(got["served"]))}
    for name in which:
        if name in ("sound", "unforced"):
            over = rows
            served, logits, routed, attn = (
                got["served"], got["model_logits"],
                last_choice(got["routed_tail"]), got["attention_tail"])
        else:
            over = rows[:CONTROL_ROWS]
            stand_in = reference_of(params, over, spec, None, tail,
                                    **control_trinity.STAND_INS[name])
            logits, routed = stand_in["logits"], tail_of(stand_in, 1)
            served = logits.argmax(-1)
            # in attention_tail's layout, the kept layers filled in
            kept = stand_in["operators"]
            shape = next(iter(kept.values())).shape
            attn = np.zeros((shape[0], layers) + shape[1:], np.float32)
            for layer, a in kept.items():
                attn[:, layer] = a
            del stand_in
        ref = reference_of(params, over, spec,
                           None if name == "unforced" else routed, tail)
        out[name] = compare(served, logits, attn, ref, spec, tr,
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
