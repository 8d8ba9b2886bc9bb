"""chip_smoke.py — does the system still start on the chip?

One process drives the normal path once, through the entry points a user
calls, at the width of a dim-2048 transformer LM: train a few steps,
score with the model ``fit`` returned, serve a classifier of the same
trunk over HTTP, compile the grouped-query flash call (causal, and under a
sliding window at 16 fetch blocks a side), the 1536-wide and the 896-wide
grouped products and the routed experts' gather combine, boost a
HIGGS-shaped forest at 63 and 255 bins, and
run a fused featurize -> booster pipeline. Weights are random from a
seed, data is synthetic, nothing touches the network. Every leg is
fatal: a failure propagates, the exit code is non-zero and no result
line is printed.

    python chip_smoke.py

needs a TPU (there is no CPU mode; tests/test_chip_smoke.py runs the leg
functions at tiny sizes instead). With several local devices the same
process uses all of them: learner mesh data x fsdp, data-parallel GBDT,
TPUModel over the default mesh. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}

preceded by one JSON line with each leg's wall and facts. Speeds printed
on the way are information, not thresholds.
"""

from __future__ import annotations

import json
import math
import socket
import sys
import time
import urllib.request

import numpy as np

# a dim-2048 LM and a HIGGS-shaped table
FULL = {
    "lm_spec": {"type": "transformer", "vocab_size": 32000, "dim": 2048,
                "depth": 8, "heads": 16, "max_len": 1024,
                "head_dtype": "bfloat16"},
    "lm_batch": 8,
    "lm_steps_per_epoch": 4,      # one epoch is one device dispatch
    "lm_epochs": 2,               # the first compiles, the second is timed
    "transform_rows": 4,
    "serve_classes": 16,
    "serve_requests": 6,
    "gbdt_rows": 1_000_000,
    "gbdt_valid_rows": 100_000,
    "gbdt_features": 28,
    "gbdt_leaves": 63,
    "gbdt_iterations": 8,
    "gbdt_max_bins": (63, 255),   # the direct kernel, the nibble kernel
    "gbdt_hist_method": "auto",
    "gbdt_min_auc": 0.75,
    "pipeline_rows": 2000,
    # the grouped-query flash call and an expert product whose width is
    # no multiple of the kernel's 1024 tile (LFM2-24B-A2B's shapes)
    "gqa": {"batch": 1, "length": 2048, "heads": 32, "kv_heads": 8,
            "head_dim": 64},
    "grouped": {"rows": 4096, "groups": 8, "k": 2048, "n": 1536},
    # a sliding-window grouped-query flash call at 16 fetch blocks a
    # side and the two products of an expert 896 wide under a hidden
    # size of 2304 (Mellum2-12B-A2.5B's shapes: tiles of 768 and 896)
    "swa": {"batch": 1, "length": 16384, "heads": 32, "kv_heads": 4,
            "head_dim": 128, "window": 1024},
    "grouped_narrow": [{"rows": 4096, "groups": 8, "k": 2304, "n": 896},
                       {"rows": 4096, "groups": 8, "k": 896, "n": 2304}],
    # gate, up and silu * up of a pass as one kernel, at both cells'
    # widths (LFM2's 2048 -> 1536, Mellum2's 2304 -> 896), written into
    # the second of three slices of a buffer
    "fused_swiglu": [{"rows": 4096, "groups": 8, "k": 2048, "n": 1536},
                     {"rows": 4096, "groups": 8, "k": 2304, "n": 896},
                     # a pass of Trinity-Mini's: 32,768 rows that fall
                     # to 16 of the 128 experts (2048 -> 1024)
                     {"rows": 32768, "groups": 128, "k": 2048, "n": 1024,
                      "live": [48, 16]}],
    # the routed experts' combine where every expert is held, at the
    # LFM2 cell's shape: 32,768 tokens x 4 rows of 2048 float32 a layer
    "combine": {"tokens": 32768, "k": 4, "dim": 2048, "passes": 4},
    # ... and the down product of every pair of a layer in one call, at
    # the LFM2 and the Mellum2 cell's shape, against the passes' calls
    "layer_down": [
        {"rows": 131072, "passes": 4, "groups": 64, "k": 1536, "n": 2048},
        {"rows": 262144, "passes": 8, "groups": 64, "k": 896, "n": 2304},
        {"rows": 262144, "passes": 8, "groups": 128, "k": 1024, "n": 2048}],
    # Trinity-Mini's shapes: a window of 2048 (three fetch blocks a
    # query block, two of them whole) at 16 fetch blocks a side, and
    # the down product of a layer's 262,144 pairs over 128 experts of
    # width 1024 against plain products
    "swa_wide": {"batch": 1, "length": 16384, "heads": 32, "kv_heads": 4,
                 "head_dim": 128, "window": 2048},
    "grouped_wide": {"rows": 262144, "groups": 128, "k": 1024, "n": 2048},
    # Granite-4.0-H-Micro's shapes: the chunked scan of a bucket (8 rows
    # of 1024, 64 heads of 64, a state of 128 in one group, chunks of
    # 256) against the recurrence, and the causal flash call at 1024
    # tokens, 32 query heads over 8 of 64, its scale of 1/64 folded
    # into q as 1/8 against the masked einsum at 1/64
    "ssd": {"batch": 8, "length": 1024, "heads": 64, "head_dim": 64,
            "state": 128, "groups": 1, "chunk": 256},
    "gqa_scaled": {"batch": 8, "length": 1024, "heads": 32, "kv_heads": 8,
                   "head_dim": 64, "multiplier": 1 / 64},
}

# a bf16 forward against the float32 reference, as relative L2 error of
# the logits: 8 layers of bf16 matmuls measured 3e-3 on a v5e (PR 21)
BF16_REL_TOL = 2e-2
# a served class may differ from the reference's only where the
# reference itself is that close to a tie
BF16_TIE_MARGIN = 0.1


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# leg 1: the device
# ---------------------------------------------------------------------------


def leg_device() -> dict:
    import importlib.metadata

    import jax
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
         f"libtpu={libtpu} platform={device['platform']} "
         f"device_kind={device['kind']!r} devices={device['count']}")
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; jax found platform "
            f"{device['platform']!r}")
    return device


# ---------------------------------------------------------------------------
# the float32 reference: same weights, dense attention, exact matmuls
# ---------------------------------------------------------------------------


def reference_forward(spec: dict, variables, tokens: np.ndarray
                      ) -> np.ndarray:
    """The plain jax.numpy path of the same network: float32 module,
    ``dense_attention`` (FLASH_MIN_LEN is the documented switch that
    keeps ``attention`` off the kernel), full-precision matmuls."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.parallel import ring_attention as ra

    module = build_network({**spec, "dtype": "float32",
                            "head_dtype": "float32"})
    fn = jax.jit(lambda v, t: module.apply(v, t))
    flash_min = ra.FLASH_MIN_LEN
    ra.FLASH_MIN_LEN = 1 << 62
    tokens = jnp.asarray(tokens, jnp.int32)
    try:
        with jax.default_matmul_precision("highest"):
            lowered = fn.lower(variables, tokens)
            assert "tpu_custom_call" not in lowered.as_text(), \
                "the reference must not run the kernel it checks"
            return np.asarray(lowered.compile()(variables, tokens))
    finally:
        ra.FLASH_MIN_LEN = flash_min


# ---------------------------------------------------------------------------
# leg 2: train
# ---------------------------------------------------------------------------


def leg_train(cfg: dict, n_dev: int):
    """``TPULearner.fit`` on the LM; returns (facts, model, tokens)."""
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.learner import TPULearner

    spec = cfg["lm_spec"]
    seq, vocab = spec["max_len"], spec["vocab_size"]
    steps = cfg["lm_steps_per_epoch"] * cfg["lm_epochs"]
    rng = np.random.default_rng(0)
    n = cfg["lm_batch"] * cfg["lm_steps_per_epoch"]
    toks = rng.integers(0, vocab, size=(n, seq)).astype(np.float32)
    tgts = np.roll(toks.astype(np.int64), -1, axis=1)
    sharded = {"meshAxes": {"data": n_dev // 2, "fsdp": 2},
               "paramSharding": "fsdp"} if n_dev > 1 else {}
    learner = TPULearner(
        networkSpec=spec, loss="token_cross_entropy",
        batchSize=cfg["lm_batch"], learningRate=1e-3, optimizer="adamw",
        computeDtype="bfloat16", epochs=cfg["lm_epochs"], logEvery=1,
        dataFeed="device", **sharded)
    model = learner.fit(DataTable({"features": toks, "label": tgts}))

    losses = [h["loss"] for h in learner.history]
    assert len(losses) == steps, (len(losses), steps)
    assert all(math.isfinite(v) for v in losses), losses
    # random weights on random tokens: the first loss is ln(vocab) give
    # or take the logits' variance
    assert abs(losses[0] - math.log(vocab)) < 1.0, losses
    timing = learner.timing
    assert timing.get("steps_timed", 0) > 0 and \
        timing.get("examples_per_sec", 0) > 0, timing
    assert not timing.get("includes_compile"), timing
    facts = {
        "steps": steps,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "tokens_per_sec_per_chip":
            round(timing["examples_per_sec"] * seq / n_dev, 1),
    }
    _log(f"train: information only, no threshold: "
         f"{facts['tokens_per_sec_per_chip']} tokens/s/chip")
    return facts, model, toks


# ---------------------------------------------------------------------------
# leg 3: transform
# ---------------------------------------------------------------------------


def leg_transform(cfg: dict, model, toks: np.ndarray, n_dev: int) -> dict:
    import jax

    from mmlspark_tpu.core.table import DataTable

    spec = cfg["lm_spec"]
    rows = toks[:cfg["transform_rows"]]
    scores = np.asarray(
        model.transform(DataTable({"features": rows}))["scores"])
    assert scores.shape == (len(rows), spec["max_len"],
                            spec["vocab_size"]), scores.shape
    assert np.isfinite(scores).all()
    ref = reference_forward(spec, model.get("weights"), rows[:1])
    rel = float(np.linalg.norm(scores[0] - ref[0])
                / np.linalg.norm(ref[0]))
    assert rel < BF16_REL_TOL, \
        f"logits off the float32 reference: rel L2 {rel:.3e}"
    facts = {"rows": len(rows), "rel_l2_vs_f32": round(rel, 5),
             "tolerance": BF16_REL_TOL,
             "argmax_agreement": round(float(np.mean(
                 scores[0].argmax(-1) == ref[0].argmax(-1))), 4)}
    if n_dev > 1:
        # replicated over the default mesh: one copy on every device
        host_bytes = sum(
            int(np.asarray(a).nbytes) for a in
            jax.tree_util.tree_leaves(model.get("weights")))
        assert model.resident_bytes() == n_dev * host_bytes, \
            (model.resident_bytes(), n_dev, host_bytes)
        facts["weight_copies"] = n_dev
    return facts


# ---------------------------------------------------------------------------
# leg 4: serve
# ---------------------------------------------------------------------------


def _post(address: str, body: dict) -> dict:
    req = urllib.request.Request(
        address, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.status == 200, resp.status
        return json.loads(resp.read())


def leg_serve(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline
    from mmlspark_tpu.serving.server import serve_model

    spec = {**cfg["lm_spec"], "num_classes": cfg["serve_classes"],
            "dtype": "bfloat16"}
    seq = spec["max_len"]
    module = build_network(spec)
    variables = jax.jit(module.init)(jax.random.PRNGKey(1),
                                     jnp.zeros((1, seq), jnp.int32))
    model = TPUModel.from_flax(module, variables, inputCol="features",
                               outputCol="scores",
                               batchSize=cfg["lm_batch"])
    rng = np.random.default_rng(1)
    toks = rng.integers(0, spec["vocab_size"],
                        size=(cfg["serve_requests"], seq))
    compiles = model.warmup({"features": toks[:1].astype(np.float32)})
    assert compiles == len(model.bucket_sizes()), compiles
    misses = model.jit_cache_misses

    engine = serve_model(json_scoring_pipeline(model, field="features"),
                         port=0, batch_size=cfg["lm_batch"])
    try:
        address = engine.source.address
        preds = [_post(address, {"features": row.tolist()})["prediction"]
                 for row in toks]
    finally:
        engine.stop()
    assert not engine.is_alive()
    with socket.socket() as s:
        assert s.connect_ex(("127.0.0.1", engine.source.port)) != 0, \
            "the server still listens after stop()"
    recompiles = model.jit_cache_misses - misses
    assert recompiles == 0, f"{recompiles} recompiles under traffic"

    ref = reference_forward(spec, variables, toks)      # (n, classes)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    agree = np.asarray(preds) == ref.argmax(-1)
    assert (agree | (margin < BF16_TIE_MARGIN)).all(), \
        (preds, ref.argmax(-1).tolist(), margin.tolist())
    assert agree.sum() * 2 > len(preds), (preds, ref.argmax(-1).tolist())
    return {"requests": len(preds),      # every one answered 200
            "predictions_equal_reference": int(agree.sum()),
            "warmup_compiles": compiles,
            "recompiles_under_traffic": recompiles}


# ---------------------------------------------------------------------------
# leg 5: GBDT + fused pipeline
# ---------------------------------------------------------------------------


def leg_kernels(cfg: dict) -> dict:
    """What ``hybrid_moe_lm`` asks of the chip beyond what the legs
    above compile: the flash forward with fewer key/value heads than
    query heads (no repeated copy of K and V) against the einsum on
    repeated K and V, the same under a sliding window (the band of key
    blocks alone) against the masked einsum, the grouped product at
    widths the 1024 tile does not divide (1536; 2304 and 896) and over 128
    groups of width 1024 against a loop over the groups, the routed experts' gather combine against
    the scatter-add form, their layer-wide down product against the
    per-pass form, a pass's gate and up products and silu * up as
    one kernel against the float32 reference, and a Mamba-2 layer's
    chunked scan against the recurrence with the flash call its
    attention layers make (a scale of their own folded into q)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.grouped_matmul import _tile, grouped_matmul
    from mmlspark_tpu.parallel.ring_attention import (
        attention, dense_attention)
    g = cfg["gqa"]
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (g["batch"], g["length"], g["heads"],
                                    g["head_dim"]), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (g["batch"], g["length"], g["kv_heads"],
                                   g["head_dim"]), jnp.bfloat16)
            for kk in keys[1:3])
    got = jax.jit(lambda q, k, v: attention(q, k, v, causal=True))(q, k, v)
    want = jax.jit(lambda q, k, v: dense_attention(q, k, v, True))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    gqa_err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
    assert gqa_err < BF16_REL_TOL, f"grouped-query flash: {gqa_err}"
    swa_err = _windowed_gap(cfg["swa"])
    assert swa_err < BF16_REL_TOL, f"windowed grouped-query flash: {swa_err}"
    wide_err = _windowed_gap(cfg["swa_wide"])
    assert wide_err < BF16_REL_TOL, f"a window of two fetch blocks: {wide_err}"
    gm_err = _grouped_gap(cfg["grouped"], keys[3:5])
    narrow = [_grouped_gap(m, keys[3:5]) for m in cfg["grouped_narrow"]]
    fused = [_fused_swiglu_gap(m, keys[3:5]) for m in cfg["fused_swiglu"]]
    ssd = _ssd_gap(cfg["ssd"])
    for what in ("vs_recurrence", "kernel_vs_jnp"):
        assert max(ssd[what]) < BF16_REL_TOL, f"ssd_scan {what}: {ssd[what]}"
    m = cfg["grouped"]
    return {"gqa_rel_l2_vs_f32": gqa_err, "grouped_rel_l2": gm_err,
            "grouped_tiles_k_n": [_tile(m["k"], 1024), _tile(m["n"], 1024)],
            "swa_rel_l2_vs_f32": swa_err,
            "swa_wide_rel_l2_vs_f32": wide_err,
            "grouped_wide_rel_l2": _grouped_gap(cfg["grouped_wide"],
                                                keys[3:5]),
            "grouped_narrow_rel_l2": narrow,
            "grouped_narrow_tiles_k_n": [
                [_tile(m["k"], 1024), _tile(m["n"], 1024)]
                for m in cfg["grouped_narrow"]],
            # [into float32, into bfloat16] a shape
            "fused_swiglu_rel_l2": [f[:2] for f in fused],
            # values of the rows read through ids that differ from the
            # gathered rows' (none may)
            "fused_swiglu_ids_differ": [f[2] for f in fused],
            "combine_rel_l2_vs_scatter_add": _combine_gap(cfg["combine"]),
            "layer_down_rel_l2": [_layer_down_gap(m)
                                  for m in cfg["layer_down"]],
            # [y, the final states] of ``ssd_scan`` against the
            # recurrence, and of the kernel against the ``jax.numpy``
            # steps (0 where the kernel does not run: off a TPU)
            "ssd_rel_l2_vs_recurrence": ssd["vs_recurrence"],
            "ssd_kernel_rel_l2_vs_jnp": ssd["kernel_vs_jnp"],
            "ssd_kernel_runs": ssd["kernel_runs"],
            "gqa_scaled_rel_l2_vs_f32": _scaled_gap(cfg["gqa_scaled"])}


def _ssd_gap(g: dict) -> dict:
    """``ssd_scan`` as the model calls it (x, B and C in bfloat16, the
    step and the decays float32, the D skip; on a TPU the kernel)
    against the recurrence in float32 at highest precision, and against
    its own ``jax.numpy`` steps: relative L2 of y and of the final
    states."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.ssd_scan import (
        _chunked, kernel_runs, recurrence, ssd_scan)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    shape = (g["batch"], g["length"])
    x = jax.random.normal(keys[0], shape + (g["heads"], g["head_dim"]),
                          jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], shape + (g["heads"],))
                         - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (g["heads"],), minval=0.0,
                                    maxval=math.log(16.0)))
    b, c = (jax.random.normal(kk, shape + (g["groups"], g["state"]),
                              jnp.bfloat16) for kk in keys[3:5])
    d = jnp.ones((g["heads"],))
    y, final = jax.jit(lambda *v: ssd_scan(*v, g["chunk"], d))(
        x, dt, a, b, c)
    steps = jax.jit(lambda *v: _chunked(*v, g["chunk"], d))(x, dt, a, b, c)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *v: recurrence(*v, d))(x, dt, a, b, c)

    def gaps(refs):
        return [float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
                for got, ref in zip((y, final), refs)]
    return {"vs_recurrence": gaps(want), "kernel_vs_jnp": gaps(steps),
            "kernel_runs": kernel_runs(g["heads"], g["head_dim"],
                                       g["state"], g["groups"], g["chunk"])}


def _scaled_gap(g: dict) -> float:
    """The causal grouped-query flash forward with q scaled by
    multiplier x sqrt(head_dim) (the kernel's own 1 / sqrt(head_dim)
    makes the rest) against softmax(q k^T multiplier) v written out in
    float32, key/value head h // (H / H_kv) for query head h."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.parallel.ring_attention import attention
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    shape = (g["batch"], g["length"])
    q = jax.random.normal(keys[0], shape + (g["heads"], g["head_dim"]),
                          jnp.bfloat16)
    k, v = (jax.random.normal(kk, shape + (g["kv_heads"], g["head_dim"]),
                              jnp.bfloat16) for kk in keys[1:3])
    fold = g["multiplier"] * math.sqrt(g["head_dim"])
    got = jax.jit(lambda q, k, v: attention(
        (q.astype(jnp.float32) * fold).astype(q.dtype), k, v,
        causal=True))(q, k, v)

    def plain(q, k, v):
        group = g["heads"] // g["kv_heads"]
        k, v = (jnp.repeat(t.astype(jnp.float32), group, axis=2)
                for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                       precision="highest") * g["multiplier"]
        seen = jnp.tril(jnp.ones((g["length"], g["length"]), bool))
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v, precision="highest")
    want = jax.jit(plain)(q, k, v)
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def _windowed_gap(g: dict) -> float:
    """The flash forward under a sliding window (the band of key blocks
    alone is visited) against the masked einsum, a block of queries at
    a time over the keys that block can see."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import tile_plan
    from mmlspark_tpu.parallel.ring_attention import (
        attention, dense_attention)
    length, window = g["length"], g["window"]
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (g["batch"], length, g["heads"],
                                    g["head_dim"]), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (g["batch"], length, g["kv_heads"],
                                   g["head_dim"]), jnp.bfloat16)
            for kk in keys[1:3])
    got = jax.jit(lambda q, k, v: attention(
        q, k, v, causal=True, window=window))(q, k, v)
    step = min(1024, length)
    dense = jax.jit(dense_attention, static_argnums=(3, 4, 5, 6))
    num = den = 0.0
    for lo in range(0, length, step):
        first = max(0, lo - window + 1)
        want = dense(q[:, lo:lo + step].astype(jnp.float32),
                     k[:, first:lo + step].astype(jnp.float32),
                     v[:, first:lo + step].astype(jnp.float32),
                     True, lo, first, window)
        num += float(jnp.sum((got[:, lo:lo + step].astype(jnp.float32)
                              - want) ** 2))
        den += float(jnp.sum(want ** 2))
    counts = tile_plan(length, length, g["head_dim"], True,
                       window=window).counts()
    _log(f"windowed flash: {counts['blocks_run']} fetch blocks a (row, "
         f"head) of {counts['blocks_grid']} grid steps")
    return math.sqrt(num / den)


def _uneven_sizes(m: dict) -> np.ndarray:
    """Group sizes in the ratio 0 : 1 : 2, repeated: every third group
    empty, the last 8 rows or more in no group. With ``live`` (first,
    count) only those groups have rows, as in a pass whose sorted rows
    fall to a few of the experts."""
    first, count = m.get("live", (0, m["groups"]))
    share = np.zeros(m["groups"], np.int64)
    share[first:first + count] = np.arange(count) % 3
    return (share * (m["rows"] - 8) // max(1, share.sum())).astype(np.int32)


def _grouped_gap(m: dict, keys) -> float:
    """``grouped_matmul`` over uneven groups (one empty, the last rows
    in no group) against a loop over the groups."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
    lhs = jax.random.normal(keys[0], (m["rows"], m["k"]), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (m["groups"], m["k"], m["n"]),
                            jnp.bfloat16) * m["k"] ** -0.5
    sizes = _uneven_sizes(m)
    out = jax.jit(lambda a, b, s: grouped_matmul(a, b, s, jnp.float32))(
        lhs, rhs, jnp.asarray(sizes))
    want = np.zeros((m["rows"], m["n"]), np.float32)
    lo = 0
    for e, size in enumerate(sizes):
        want[lo:lo + size] = np.asarray(jnp.einsum(
            "tk,kn->tn", lhs[lo:lo + size], rhs[e],
            preferred_element_type=jnp.float32))
        lo += size
    gm_err = float(np.linalg.norm(np.asarray(out) - want)
                   / np.linalg.norm(want))
    assert gm_err < BF16_REL_TOL, f"grouped product {m}: {gm_err}"
    assert not np.asarray(out[lo:]).any(), "rows of no group are not zero"
    return gm_err


def _fused_swiglu_gap(m: dict, keys) -> list:
    """``grouped_swiglu`` over uneven groups (one empty, the last rows
    in no group) into the middle slice of a buffer, against
    silu(x @ w_gate[g]) * (x @ w_up[g]) a group in float32; the other
    slices are left as they were. Into a float32 buffer (the kernel's
    arithmetic alone, beside ``grouped_rel_l2``) and into a bfloat16 one
    (as ``routed_experts`` calls it: the one cast's rounding on top).
    The rows are those of a table of ``rows / 2`` tokens that shuffled
    ids name, each token twice; the same call reading them through the
    ids (as ``routed_experts`` calls it) has to give the bfloat16
    buffer of the gathered rows to the bit: [float32 gap, bfloat16
    gap, values that differ]."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.grouped_matmul import grouped_swiglu, row_table
    rows, n = m["rows"], m["n"]
    u = jax.random.normal(keys[0], (rows // 2, m["k"]), jnp.bfloat16)
    tok = jax.random.permutation(jax.random.fold_in(keys[0], 1), rows) // 2
    x = u[tok]
    w_gate, w_up = (jax.random.normal(k, (m["groups"], m["k"], n),
                                      jnp.bfloat16) * m["k"] ** -0.5
                    for k in jax.random.split(keys[1]))
    sizes = _uneven_sizes(m)
    f32 = jnp.float32
    want, lo = np.zeros((int(sizes.sum()), n), np.float32), 0
    for e, size in enumerate(sizes):
        rows_e = x[lo:lo + size].astype(f32)
        want[lo:lo + size] = np.asarray(
            jax.nn.silu(jnp.dot(rows_e, w_gate[e].astype(f32),
                                precision=jax.lax.Precision.HIGHEST))
            * jnp.dot(rows_e, w_up[e].astype(f32),
                      precision=jax.lax.Precision.HIGHEST))
        lo += size
    gaps = []
    for dtype in (f32, jnp.bfloat16):
        out = np.asarray(jax.jit(lambda x, *a: grouped_swiglu(
            row_table(x, rows), *a, tok=jnp.arange(rows, dtype=jnp.int32)))(
            x, w_gate, w_up, jnp.asarray(sizes),
            jnp.full((3 * rows, n), 7, dtype), jnp.int32(rows)).astype(f32))
        gaps.append(float(np.linalg.norm(out[rows:rows + lo] - want)
                          / np.linalg.norm(want)))
        assert (out[:rows] == 7).all() and (out[2 * rows:] == 7).all(), \
            "a slice of the buffer that is not the pass's was written"
    assert gaps[0] < 1e-5 and gaps[1] < BF16_REL_TOL, \
        f"fused gate, up and silu * up {m}: {gaps}"
    through_ids = jax.jit(lambda u, tok, *a: grouped_swiglu(
        row_table(u, rows), *a, tok=tok))(
        u, tok, w_gate, w_up, jnp.asarray(sizes),
        jnp.full((3 * rows, n), 7, jnp.bfloat16), jnp.int32(rows))
    differ = int(np.sum(np.asarray(through_ids.astype(f32)) != out))
    assert differ == 0, f"rows read through ids {m}: {differ} differ"
    return gaps + [differ]


def _combine_gap(c: dict) -> float:
    """The gather combine of ``routed_experts`` (every expert held)
    against the scatter-add form on the same sorted rows: the two differ
    by the order of a k-term float32 sum."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mmlspark_tpu.models.expert_layer import _gather_combine
    t, k, dim = c["tokens"], c["k"], c["dim"]
    rows = t * k // c["passes"]
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    out_all = jax.random.normal(keys[0], (t * k, dim), jnp.float32)
    order = jax.random.permutation(keys[1], t * k)
    gates = jax.random.uniform(keys[2], (t, k), jnp.float32)

    def scatter_add(out_all, order, gates):
        def one_pass(i, y):
            at = lax.dynamic_slice_in_dim(order, i * rows, rows)
            out = lax.dynamic_slice_in_dim(out_all, i * rows, rows)
            return y.at[at // k].add(out * gates.reshape(-1)[at][:, None])
        return lax.fori_loop(0, c["passes"], one_pass,
                             jnp.zeros((t, dim), jnp.float32))
    got = jax.jit(_gather_combine)(out_all, order, gates)
    want = jax.jit(scatter_add)(out_all, order, gates)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap < 1e-6, f"gather combine against the scatter-add: {gap}"
    return gap


def _layer_down_gap(m: dict) -> float:
    """The down product where every expert is held: one
    ``grouped_matmul`` over every pair of a layer, no row zeroed (every
    row is some group's), against what ``routed_experts`` ran before:
    a call a pass, each ending in its zeroing ``where``, copied into a
    zero-filled buffer. Uneven groups, one empty. The same products in
    the same order: 0 expected."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
    rows, passes = m["rows"], m["passes"]
    per = rows // passes
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    lhs = jax.random.normal(keys[0], (rows, m["k"]), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (m["groups"], m["k"], m["n"]),
                            jnp.bfloat16) * m["k"] ** -0.5
    share = np.random.default_rng(3).random(m["groups"])
    share[1] = 0.0
    load = np.floor(share / share.sum() * rows).astype(np.int32)
    load[-1] += rows - load.sum()

    def gap(lhs, rhs, load):
        whole = grouped_matmul(lhs, rhs, load, jnp.float32,
                               rest_unread=True)
        ends = jnp.cumsum(load)

        def one_pass(i, acc):
            lo = i * per
            sizes = jnp.clip(ends - lo, 0, per) \
                - jnp.clip(ends - load - lo, 0, per)
            out = grouped_matmul(lax.dynamic_slice_in_dim(lhs, lo, per),
                                 rhs, sizes, jnp.float32)
            return lax.dynamic_update_slice_in_dim(acc, out, lo, 0)
        by_pass = lax.fori_loop(0, passes, one_pass,
                                jnp.zeros((rows, m["n"]), jnp.float32))
        return jnp.linalg.norm(whole - by_pass) / jnp.linalg.norm(by_pass)
    got = float(jax.jit(gap)(lhs, rhs, jnp.asarray(load)))
    assert got <= 1e-7, f"layer-wide down product {m}: {got}"
    return got


def _auc(y: np.ndarray, score: np.ndarray) -> float:
    """Mann-Whitney AUC (ties are measure-zero for float scores)."""
    ranks = np.empty(len(score))
    ranks[np.argsort(score)] = np.arange(1, len(score) + 1)
    pos = y > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def leg_gbdt(cfg: dict, n_dev: int) -> dict:
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.parallel import mesh as mesh_lib

    rng = np.random.default_rng(0)
    n_tr, n = cfg["gbdt_rows"], cfg["gbdt_rows"] + cfg["gbdt_valid_rows"]
    X = rng.normal(size=(n, cfg["gbdt_features"])).astype(np.float32)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2]
             + 0.5 * np.sin(3 * X[:, 3])
             + rng.normal(scale=0.5, size=n))
    y = (logit > 0).astype(np.float64)
    mesh = mesh_lib.make_mesh({"data": n_dev}) if n_dev > 1 else None

    facts = {}
    for max_bin in cfg["gbdt_max_bins"]:
        params = {"objective": "binary",
                  "num_iterations": cfg["gbdt_iterations"],
                  "boost_chunk": cfg["gbdt_iterations"],
                  "num_leaves": cfg["gbdt_leaves"], "max_bin": max_bin,
                  "min_data_in_leaf": 50,
                  "hist_method": cfg["gbdt_hist_method"]}
        if n_dev > 1:
            params["parallelism"] = "data"
        t0 = time.perf_counter()
        booster = gbdt.train(params, X[:n_tr], y[:n_tr], mesh=mesh)
        wall = time.perf_counter() - t0
        assert booster.params["hist_method"] == "pallas", \
            booster.params["hist_method"]
        assert booster.train_info["bin_path"] == "device", \
            booster.train_info
        assert booster.train_info["bins_devices"] == n_dev, \
            booster.train_info
        pred = np.asarray(booster.predict(X[n_tr:]))
        assert pred.shape == (n - n_tr,) and np.isfinite(pred).all()
        auc = _auc(y[n_tr:], pred)
        assert auc > cfg["gbdt_min_auc"], f"holdout AUC {auc:.4f}"
        facts[f"max_bin_{max_bin}"] = {
            "wall_s": round(wall, 2), "holdout_auc": round(auc, 4),
            "phases": booster.train_timing,
            "bins_devices": booster.train_info["bins_devices"]}
    facts["pipeline"] = _fused_pipeline(cfg)
    return facts


def _fused_pipeline(cfg: dict) -> dict:
    from mmlspark_tpu import DataTable, Pipeline
    from mmlspark_tpu.automl.featurize import Featurize
    from mmlspark_tpu.gbdt import TPUBoostClassifier

    def table(n, seed):
        rng = np.random.default_rng(seed)
        num1, num2 = rng.normal(size=n), rng.normal(size=n)
        icol = rng.integers(-5, 5, n)
        return DataTable({
            "num1": num1, "num2": num2, "icol": icol,
            "label": (num1 + 0.5 * num2 + 0.1 * icol > 0).astype(float)})

    rows = cfg["pipeline_rows"]
    pm = Pipeline(stages=[
        Featurize(featureColumns=["num1", "num2", "icol"],
                  numberOfFeatures=8),
        TPUBoostClassifier(featuresCol="features", labelCol="label",
                           numIterations=8, numLeaves=7, minDataInLeaf=4,
                           histMethod=cfg["gbdt_hist_method"]),
    ]).fit(table(rows, 11))
    fused = pm.fused()
    scoring = table(rows // 2, 12)
    plan = fused.plan_for(scoring.schema)
    out = fused.transform(scoring)
    roundtrips = plan.last_roundtrips
    staged = fused.transform_staged(scoring)
    described = plan.describe()
    # the booster must sit INSIDE a fused segment ([A+B]), not beside
    # one: stage_device_op swallows a failing device_op and runs the
    # stage on the host
    segments = [seg for seg in described.split(" -> ")
                if seg.startswith("[")]
    assert len(segments) == 1 and "TPUBoost" in segments[0], described
    assert roundtrips == 1, (roundtrips, described)
    assert np.array_equal(np.asarray(out["prediction"]),
                          np.asarray(staged["prediction"]))
    prob, prob_staged = (np.asarray(t["probability"])
                         for t in (out, staged))
    assert np.allclose(prob, prob_staged, atol=1e-5)
    accuracy = float(np.mean(
        np.asarray(out["prediction"]) == np.asarray(scoring["label"])))
    assert accuracy > 0.8, accuracy
    return {"plan": described, "roundtrips": roundtrips,
            "bit_identical_to_staged": bool(
                np.array_equal(prob, prob_staged)),
            "accuracy": round(accuracy, 4)}


# ---------------------------------------------------------------------------
# placement on several devices, and the report
# ---------------------------------------------------------------------------


def device_peaks() -> list:
    import jax
    return [int(d.memory_stats()["peak_bytes_in_use"])
            for d in jax.devices()]


def assert_balanced(peaks: list, what: str) -> None:
    """Every device did a comparable share: no device's peak is under
    half the largest (device 0 alone doing the work leaves the others
    near zero)."""
    assert min(peaks) * 2 >= max(peaks), f"{what}: peaks {peaks}"


def main() -> int:
    t_start = time.perf_counter()
    legs = {}

    def run(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        facts = out[0] if isinstance(out, tuple) else out
        legs[name] = {"ok": True,
                      "wall_s": round(time.perf_counter() - t0, 2),
                      **facts}
        _log(f"leg {name} ok in {legs[name]['wall_s']} s")
        return out

    device = run("device", leg_device)
    n_dev = device["count"]

    from mmlspark_tpu.native import loader as native
    from mmlspark_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

    cfg = FULL
    _, model, toks = run("train", leg_train, cfg, n_dev)
    if n_dev > 1:
        legs["train"]["device_peaks"] = device_peaks()
        assert_balanced(legs["train"]["device_peaks"], "train")
    run("transform", leg_transform, cfg, model, toks, n_dev)
    del model
    run("serve", leg_serve, cfg)
    run("kernels", leg_kernels, cfg)
    run("gbdt", leg_gbdt, cfg, n_dev)

    peaks = device_peaks()      # device 0 also ran the references
    print(json.dumps({
        "legs": legs,
        "native": "loaded" if native.available() else "absent",
        "compile_cache_dir": cache_dir,
        "peak_hbm_bytes": max(peaks),
        "peak_hbm_bytes_per_device": peaks,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
