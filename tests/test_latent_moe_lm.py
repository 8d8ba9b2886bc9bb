"""``latent_moe_lm`` against the plain reference, on the CPU at a tiny
preset that has every kind of layer the configuration has: hidden 64,
4 heads, 16 experts of which a chip holds 4, ``index_topk`` 8, rows of
32 (four times ``index_topk``), layers (dense, full), 3 x (sparse,
shared), (sparse, full).

The reference (benchmark/reference_glm_dsa.py) imports nothing of the
program. In float32 the two agree to rounding, so the selected sets and
the router's choices are compared exactly; in bfloat16 a near tie may
fall the other way, and they are compared where the reference's margin
is wider than what bfloat16 can move.
"""

import os
import sys

import numpy as np
import pytest

from mmlspark_tpu.models.networks import (
    NETWORK_REGISTRY, build_network)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import ROWS, TINY, apply, tiny_with_reference  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    return tiny_with_reference()


def test_registered_and_built_from_a_spec(tiny):
    module, params, _ = tiny
    assert "latent_moe_lm" in NETWORK_REGISTRY
    assert module.int_input and module.cfg.indexer_types == (
        "full", "shared", "shared", "shared", "full")
    with pytest.raises(ValueError, match="indexer_types"):
        apply(build_network({**TINY, "indexer_types": ["shared"] * 5}),
              params, ROWS)


def test_logits_match_the_reference_in_float32(tiny):
    module, params, ref = tiny
    logits = apply(module, params, ROWS)
    assert logits.shape == (3, 128) and logits.dtype == np.float32
    assert np.linalg.norm(logits - ref["logits"]) \
        < 1e-5 * np.linalg.norm(ref["logits"])


@pytest.mark.parametrize("layer", [0, 4])
def test_selected_sets_match_the_reference(tiny, layer):
    module, params, ref = tiny
    keep = apply(module, params, ROWS, capture=f"selected_{layer}")
    assert keep.shape == (3, 32, 32) and keep.dtype == np.bool_
    # every key s <= t while t < index_topk, exactly index_topk after
    assert (keep.sum(-1) == np.minimum(np.arange(32) + 1, 8)).all()
    assert not np.triu(keep, 1).any()
    assert (keep == ref["selected"][layer]).all()
