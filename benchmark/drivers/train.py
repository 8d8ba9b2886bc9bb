"""Driver of the training cells: one ``TPULearner.fit`` is the run.

``fit`` cannot be stopped at a deadline, so the traffic file states the
rate the parent sustains (``nominal_steps_per_s``) and the run asks for
the epochs that fill ``--seconds`` at that rate: a fixed amount of work
from the seed. The first dispatch (one epoch, which compiles or loads
the program) is warm-up and ends set-up; ``learner.timing`` clocks every
later step up to ``block_until_ready`` of the last state.

``correct`` has the plain reference (AdamW in float32 from the same
initial tree and the same rows) follow the first epoch, one dispatch.
Set-up drives the learner through that epoch in a ``fit`` of its own,
which returns the weights after it, and hands the same learner to the
window, whose ``fit`` starts from the seed again. Compared: the window's
first losses against that fit's (the same steps: limit 0), the window's
second loss and the mean of its first three against the reference's,
and the norm of every leaf's change over the epoch against the
reference's, by the worst leaf and by the median leaf. ``fit`` lets no
gradient and no optimizer state out, so the first gradient's norm is
not compared (PERF.md).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from loadgen import fold_seed as seed31   # seeds over 2**31 are folded


def make_rows(seed: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """The run's data: ``--seed`` draws the token rows."""
    return np.random.default_rng(seed31(seed)).integers(
        0, vocab, size=(rows, seq))


def first_batches(seed: int, rows: int, batch: int, steps: int):
    assert steps * batch <= rows, "the compared steps lie in epoch 0"
    """Row indices of the first steps. ``fit`` shuffles on the device
    with ``permutation(fold_in(PRNGKey(seed + 17), epoch), rows)`` and
    offers no way to ask for the order, so it is restated here (PERF.md,
    Open questions)."""
    import jax
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(jax.random.PRNGKey(seed31(seed) + 17), 0), rows))
    return [perm[b * batch:(b + 1) * batch] for b in range(steps)]


def initial_params(spec: dict, seed: int):
    """The tree ``fit`` starts from: the network's own seeded
    initializer, the same call the learner makes."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": "bfloat16", **spec})
    seq = spec["max_len"]
    return jax.jit(lambda: module.init(
        jax.random.PRNGKey(seed31(seed)),
        jnp.zeros((1, seq), jnp.int32), train=False))()["params"]


def change_gaps(got: dict, want: dict) -> dict:
    """Each leaf's gap between the program's norm of change and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger; over the leaves that the
    reference moves at all."""
    moved = [k for k, v in want.items() if v > 0]
    floor = float(np.median([want[k] for k in moved]))
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in moved}


def compare(window_losses, setup_losses, got_change, ref, limits: dict
            ) -> tuple:
    """``window_losses`` and ``setup_losses`` are the first epoch's as
    the window's fit and set-up's fit logged them; ``got_change`` the
    norms of the leaves' change over set-up's fit; ``ref`` what
    ``reference.train_follow`` returned. Of the first three losses the
    second and the mean of the three are held to a limit: the first and
    the third alone have no reading to set one from (PERF.md). The
    worst leaf's gap catches a leaf that did not move or moved double;
    the median leaf's is steady from seed to seed and tells bfloat16
    from the precision below."""
    bad = lambda v: not math.isfinite(v)             # noqa: E731
    gaps = [math.inf if bad(got) else abs(got - want)
            for got, want in zip(window_losses[:3], ref["losses"])]
    values = {"loss2_gap": gaps[1], "loss_mean_gap": sum(gaps) / 3}
    values["replay_gap"] = max(
        math.inf if bad(a) or bad(b) else abs(a - b)
        for a, b in zip(window_losses, setup_losses))
    leaves = change_gaps(got_change, ref["change_norms"])
    worst = max(leaves, key=leaves.get)
    values["change_gap"] = leaves[worst]
    values["change_median_gap"] = float(np.median(list(leaves.values())))
    return ([{"name": k, "value": v, "limit": limits[k]}
             for k, v in values.items()], worst)


def make_learner(spec: dict, tr: dict):
    from mmlspark_tpu.models.learner import TPULearner
    # the learner bakes its seed into its compiled programs (the init
    # key and the shuffle key are constants of the HLO), so a new seed
    # is a new compile of over a minute: the learner's seed is fixed in
    # the traffic file and ``--seed`` draws the data (PERF.md)
    return TPULearner(
        networkSpec=spec, loss="token_cross_entropy",
        batchSize=tr["batch"], optimizer="adamw",
        learningRate=tr["learning_rate"], weightDecay=tr["weight_decay"],
        schedule="constant", computeDtype="bfloat16", epochs=1,
        logEvery=1, dataFeed="device", seed=tr["learner_seed"])


def first_epoch(learner, table) -> tuple:
    """Set-up's fit: one epoch from the seed. Its losses, and the
    weights after it, on the host."""
    learner.set("epochs", 1)
    model = learner.fit(table)
    return ([h["loss"] for h in learner.history],
            model.get("weights")["params"])


def change_of(after, spec: dict, tr: dict, moved) -> dict:
    """The norms of the program's change from the initial tree."""
    import reference
    return reference.change_norms(
        after, initial_params(spec, tr["learner_seed"]), moved)


def follow(spec: dict, tr: dict, toks, tgts, **kw) -> dict:
    """The reference over the first epoch's batches."""
    import reference
    rows, batch = tr["rows"], tr["batch"]
    order = first_batches(tr["learner_seed"], rows, batch, rows // batch)
    return reference.train_follow(
        initial_params(spec, tr["learner_seed"]),
        [(toks[idx], tgts[idx]) for idx in order], spec["heads"],
        tr["learning_rate"], tr["weight_decay"], **kw)


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import jax
    from mmlspark_tpu.core.table import DataTable

    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    rows, batch = tr["rows"], tr["batch"]
    seq, vocab = spec["max_len"], spec["vocab_size"]
    per_epoch = rows // batch
    window = min(seconds, tr["trace_window_s"]) if trace_dir else seconds
    epochs = 1 + max(1, math.ceil(
        window * tr["nominal_steps_per_s"] / per_epoch))

    toks = make_rows(seed, rows, seq, vocab)
    tgts = np.roll(toks, -1, axis=1)
    table = DataTable({"features": toks.astype(np.float32),
                       "label": tgts.astype(np.int64)})
    learner = make_learner(spec, tr)
    setup_losses, after = first_epoch(learner, table)
    learner.set("epochs", epochs)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t_fit = time.time()
    try:
        model = learner.fit(table)
    finally:
        t_done = time.time()
        if trace_dir:
            jax.profiler.stop_trace()

    timing, history = learner.timing, learner.history
    steps = per_epoch * epochs
    assert len(history) == steps, (len(history), steps)
    # the learner stamps the first dispatch's log entries as its clock
    # starts: the end of warm-up, and of set-up
    t_first = history[per_epoch - 1]["time"]
    assert t_fit <= t_first <= t_done
    assert timing["steps_timed"] == steps - per_epoch, timing
    assert not timing.get("includes_compile"), timing
    assert timing["wall_s"] <= t_done - t_first + 0.05, (timing, t_done)
    tokens_per_s = timing["steps_timed"] * batch * seq / timing["wall_s"]
    losses = [h["loss"] for h in history]
    failed = sum(1 for v in losses if not math.isfinite(v))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())

    # the program's state is gone before the reference takes the chip
    del model, table
    gc.collect()
    t_ref = time.time()
    ref = follow(spec, tr, toks, tgts)
    checks, worst = compare(
        losses[:per_epoch], setup_losses,
        change_of(after, spec, tr, ref["moved"]), ref, tr["limits"])
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": t_first - t_start},
        "attempted": steps, "failed": failed, "checks": checks,
        "memory_peak_bytes": peak,
        "trace_skip_first": 1,
        "counters": {"steps_timed": timing["steps_timed"],
                     "wall_s": timing["wall_s"], "batch": batch,
                     "seq": seq, "tokens_per_s": tokens_per_s,
                     "steps_per_dispatch": per_epoch},
        "info": {"epochs": epochs, "window_s": timing["wall_s"],
                 "fit_s": t_done - t_fit, "reference_s":
                 time.time() - t_ref, "losses_first": losses[:3],
                 "reference_losses": ref["losses"][:3],
                 "loss_last": losses[-1], "change_gap_leaf": worst},
    }


def control(cell: dict, seed: int, which) -> dict:
    """What the comparison reads on this seed's rows at the cell's own
    size, with no window: 'sound' is the program's first epoch; 'fp8'
    (and, for the record, 'int8') the reference with its matmuls in the
    precision below bfloat16 in the program's place; 'half_batch' and
    'state_unchanged' the reference with that fault planted."""
    from mmlspark_tpu.core.table import DataTable
    spec = cell["config_file"]["networkSpec"]
    tr = cell["traffic_file"]
    toks = make_rows(seed, tr["rows"], spec["max_len"], spec["vocab_size"])
    tgts = np.roll(toks, -1, axis=1)
    stand_ins = {"fp8": {"matmul": "fp8"}, "int8": {"matmul": "int8"},
                 "state_unchanged": {"fault": "state_unchanged"},
                 "half_batch": {"fault": "half_batch"}}
    got = {}
    if "sound" in which:
        got["sound"] = first_epoch(make_learner(spec, tr), DataTable(
            {"features": toks.astype(np.float32),
             "label": tgts.astype(np.int64)}))
        gc.collect()
    ref = follow(spec, tr, toks, tgts)
    if "sound" in got:
        got["sound"] = (got["sound"][0], change_of(
            got["sound"][1], spec, tr, ref["moved"]))
    for name in which:
        if name != "sound":
            low = follow(spec, tr, toks, tgts, moved=ref["moved"],
                         **stand_ins[name])
            got[name] = (low["losses"], low["change_norms"])
    out = {name: compare(losses, losses, change, ref, tr["limits"])
           for name, (losses, change) in got.items()}
    return {**{name: checks for name, (checks, _) in out.items()},
            "info": {"worst_leaf": {name: worst
                                    for name, (_, worst) in out.items()}}}
