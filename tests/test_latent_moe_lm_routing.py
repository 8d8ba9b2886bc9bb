"""``latent_moe_lm``'s router against the plain reference on the CPU at
the tiny preset, float32: every expert layer chooses the reference's
experts for every token."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import ROWS, apply, tiny_with_reference  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    return tiny_with_reference()


@pytest.mark.parametrize("layer", [1, 2, 3, 4])
def test_router_choices_match_the_reference(tiny, layer):
    module, params, ref = tiny
    chosen = apply(module, params, ROWS, capture=f"routed_{layer}")
    assert chosen.shape == (3, 32, 4)
    assert (np.sort(chosen, -1) == np.sort(ref["routed"][layer], -1)).all()

