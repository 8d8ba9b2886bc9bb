"""``latent_moe_lm``'s selection on the CPU at the tiny preset: it is
real past ``index_topk`` and absent up to it."""

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


def test_the_selection_is_real(tiny):
    """Rows longer than index_topk: attention over the selected set is
    not full causal attention. Rows no longer than it: it is."""
    module, params, ref = tiny
    causal = reference.forward(params, ROWS, TINY, attend="causal")
    logits = apply(module, params, ROWS)
    gap = np.linalg.norm(logits - causal["logits"]) \
        / np.linalg.norm(causal["logits"])
    assert gap > 0.01, gap
    short = ROWS[:, :8]
    causal = reference.forward(params, short, TINY, attend="causal")
    logits = apply(module, params, short)
    assert np.linalg.norm(logits - causal["logits"]) \
        < 1e-5 * np.linalg.norm(causal["logits"])
    assert (apply(module, params, short, capture="selected_4")
            == np.tril(np.ones((8, 8), bool))).all()
