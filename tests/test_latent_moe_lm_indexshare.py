"""IndexShare in ``latent_moe_lm`` on the CPU at the tiny preset: a
``shared`` layer uses the set of the ``full`` layer below it and holds
no selector."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import (  # noqa: E402
    ROWS, TINY, apply, reference, tiny_with_reference)


@pytest.fixture(scope="module")
def tiny():
    return tiny_with_reference()


def test_a_shared_layer_uses_the_set_below_and_holds_no_selector(tiny):
    module, params, _ = tiny
    for i, kind in enumerate(TINY["indexer_types"]):
        names = set(params[f"layer_{i}_attn"])
        assert ("idx_q" in names) == (kind == "full"), (i, names)
        assert {n for n in names if n.startswith("idx_")} == (
            {"idx_q", "idx_k", "idx_k_norm_scale", "idx_k_norm_bias",
             "idx_w"} if kind == "full" else set())
    sets = [apply(module, params, ROWS, capture=f"selected_{i}")
            for i in range(5)]
    for i in (1, 2, 3):
        assert (sets[i] == sets[0]).all()
    assert (sets[4] != sets[0]).any()
    # the reference told to hand layer 0's sets to layer 4 is another
    # model: that control has something to catch
    other = reference.forward(params, ROWS, TINY, sets="first")
    assert (other["selected"][4] == sets[0]).all()
    assert np.abs(other["logits"] - tiny[2]["logits"]).max() > 1e-3
