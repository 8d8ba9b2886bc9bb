"""``latent_moe_lm`` as the cell runs it, on the CPU at the tiny preset:
bfloat16 parameters and products stay close to the reference on the
same parameters."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.models.networks import build_network

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import (  # noqa: E402
    ROWS, TINY, apply, build, reference)


def test_bfloat16_stays_close_and_differs_only_at_near_ties():
    """The program as the cell runs it: bfloat16 parameters and
    products. The sets may differ where the k-th and (k+1)-th index
    scores of the reference are nearer than MARGIN of their size."""
    _, params = build()
    module = build_network({"dtype": "bfloat16", **TINY})
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim else a, params)
    ref_low = reference.forward(low, ROWS, TINY)
    logits = apply(module, low, ROWS)
    rel = np.linalg.norm(logits - ref_low["logits"]) \
        / np.linalg.norm(ref_low["logits"])
    assert rel < 0.15, rel       # a flipped key or expert at 64 wide
    keep = apply(module, low, ROWS, capture="selected_4")
    miss = (keep & ~ref_low["selected"][4]).sum() / keep.sum()
    assert miss < 0.1, miss
