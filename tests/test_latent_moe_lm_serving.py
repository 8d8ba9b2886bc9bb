"""``latent_moe_lm`` on the normal path: ``TPUModel.transform`` with the
model's per-row counters, and ``serve_model(json_scoring_pipeline(...))``
over HTTP for one batch, against the plain reference."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import ROWS, TINY, build, reference  # noqa: E402


@pytest.fixture(scope="module")
def served():
    from mmlspark_tpu.models.tpu_model import TPUModel
    module, params = build()
    ref = reference.forward(params, ROWS, TINY)
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=4)
    return model, ref


def test_through_tpu_model_transform_with_its_row_counters(served):
    from mmlspark_tpu.core.table import DataTable
    model, ref = served
    for h in model.histograms().values():
        h.reset()
    out = model.transform(DataTable(
        {"features": ROWS.astype(np.float32)}))["scores"]
    assert np.linalg.norm(out - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])
    hists = model.histograms()
    # one entry a real row: the bucket's padded fourth row is left out
    for name in ("moe_tokens_held", "moe_load_max_over_mean",
                 "dsa_keys_per_query"):
        assert hists[name].snapshot()["count"] == 3, name
    held = sum((ref["routed"][i] // 4 == 1).sum() for i in (1, 2, 3, 4))
    assert hists["moe_tokens_held"].snapshot()["sum"] == held
    keys = hists["dsa_keys_per_query"].snapshot()
    assert keys["sum"] / keys["count"] == pytest.approx(
        np.minimum(np.arange(32) + 1, 8).mean())
    assert hists["moe_load_max_over_mean"].snapshot()["sum"] / 3 >= 1.0


def test_through_serve_model_over_http(served):
    from mmlspark_tpu.serving.fleet import json_scoring_pipeline
    from mmlspark_tpu.serving.server import serve_model
    model, ref = served
    engine = serve_model(json_scoring_pipeline(model, field="features"),
                         port=0, batch_size=4, max_wait_ms=5.0, workers=1)
    try:
        answers = []
        for row in ROWS:
            req = urllib.request.Request(
                engine.source.address,
                data=json.dumps({"features": row.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            answers.append(json.loads(
                urllib.request.urlopen(req, timeout=60).read()))
    finally:
        engine.stop()
    assert [a["prediction"] for a in answers] == \
        ref["logits"].argmax(-1).tolist()
