"""The pieces of ``latent_moe_lm`` on the CPU: the expert layer's share
against the uncut reference, the grouped product's passes, the exact
top-k, the selector's segments, and the Pallas kernel interpreted."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from latent_moe_tiny import TINY, reference  # noqa: E402


def test_the_share_adds_up():
    """One expert layer, uncut in the reference and as four shares of
    4 experts in the program: the parts that all ranks give, the shared
    expert counted once, sum to the uncut layer's output."""
    from mmlspark_tpu.models.latent_moe_lm import ExpertLayer, LatentMoEConfig
    sizes = {k: v for k, v in TINY.items() if k != "type"}
    sizes.update(indexer_types=("full",), mlp_layer_types=("sparse",),
                 dtype=jnp.float32)
    whole = LatentMoEConfig(**{**sizes, "experts_held": 16,
                               "expert_rank": 0})
    u = jax.random.normal(jax.random.PRNGKey(1), (48, 64), jnp.float32)
    params = ExpertLayer(whole).init(jax.random.PRNGKey(2), u)["params"]
    uncut, chosen, margin = reference.experts(
        jax.tree_util.tree_map(np.asarray, params),
        {**TINY, "experts_held": 16, "expert_rank": 0}, u, "f32")
    # uncut, the margin is the 4th of score + bias less the 5th
    biased = np.asarray(jax.nn.sigmoid(u @ params["router"].T)
                        + params["router_bias"])
    top = -np.sort(-biased, -1)
    np.testing.assert_allclose(margin, top[:, 3] - top[:, 4], atol=1e-6)
    shared = reference.swiglu(u, params["shared_0"], "f32")
    parts, loads = [], []
    for rank in range(4):
        cfg = LatentMoEConfig(**{**sizes, "experts_held": 4,
                                 "expert_rank": rank})
        mine = {k: (v[rank * 4:(rank + 1) * 4]
                    if k.startswith("experts_") else v)
                for k, v in params.items()}
        y, ch, load = ExpertLayer(cfg).apply({"params": mine}, u)
        assert (np.sort(ch, -1) == np.sort(chosen, -1)).all()
        parts.append(np.asarray(y))
        loads.append(np.asarray(load))
        # a rank's part is the reference's share for that rank
        want, _, margin = reference.experts(
            jax.tree_util.tree_map(np.asarray, mine),
            {**TINY, "experts_held": 4, "expert_rank": rank}, u, "f32")
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
        # a rank's margin: the least gap between a chosen and an
        # unchosen expert of which one is this rank's
        mine_e = np.arange(16) // 4 == rank
        for t in (0, 17, 47):
            inside = np.isin(np.arange(16), np.asarray(chosen[t]))
            gaps = [biased[t, a] - biased[t, b]
                    for a in np.flatnonzero(inside)
                    for b in np.flatnonzero(~inside)
                    if mine_e[a] or mine_e[b]]
            np.testing.assert_allclose(margin[t], min(gaps), atol=1e-6)
    assert np.concatenate(loads).sum() == 48 * 4      # no token dropped
    np.testing.assert_allclose(sum(parts) - 3 * np.asarray(shared), uncut,
                               rtol=1e-4, atol=1e-5)
    assert np.abs(parts[0] - np.asarray(shared)).max() > 1e-3


def test_no_token_is_dropped_when_every_token_lands_here():
    """All pairs fall to the experts held: several passes, the same
    sum as the dense one."""
    from mmlspark_tpu.models import latent_moe_lm as lm
    t, k, held, d, w = 64, 4, 4, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(keys[0], (t, d))
    chosen = jnp.tile(jnp.arange(4)[None], (t, 1)) + 8     # experts 8-11
    gates = jax.random.uniform(keys[1], (t, k))
    wg, wu = (jax.random.normal(kk, (held, d, w)) for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (held, w, d))
    assert 3 * lm._pass_rows(t * k, held, 16) < t * k   # four passes
    y, load = jax.jit(lambda *a: lm.routed_experts(*a, 8, 16))(
        u, chosen, gates, wg, wu, wd)
    want = sum(gates[:, e, None]
               * ((jax.nn.silu(u @ wg[e]) * (u @ wu[e])) @ wd[e])
               for e in range(held))
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    assert load.tolist() == [t] * held


@pytest.mark.parametrize("n_here", [0, 79, 80, 81, 160, 161])
def test_pairs_at_the_edges_of_the_passes(n_here):
    """A pass takes 80 pairs at these sizes: a pair on either side of
    each edge is counted once."""
    from mmlspark_tpu.models import latent_moe_lm as lm
    t, k, held, d, w = 64, 4, 4, 16, 8
    assert lm._pass_rows(t * k, held, 16) == 80
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    u = jax.random.normal(keys[0], (t, d))
    flat = np.arange(t * k)
    chosen = jnp.asarray(np.where(flat < n_here, 8 + flat % 4, flat % 4)
                         .reshape(t, k))
    gates = jax.random.uniform(keys[1], (t, k))
    wg, wu = (jax.random.normal(kk, (held, d, w)) for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (held, w, d))
    y, load = jax.jit(lambda *a: lm.routed_experts(*a, 8, 16))(
        u, chosen, gates, wg, wu, wd)
    want = sum(jnp.sum(jnp.where(chosen == 8 + e, gates, 0.0), -1)[:, None]
               * ((jax.nn.silu(u @ wg[e]) * (u @ wu[e])) @ wd[e])
               for e in range(held))
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    assert int(load.sum()) == n_here


def test_exact_topk_mask_is_a_stable_sort_s_top_k():
    from mmlspark_tpu.ops.sparse_select import exact_topk_mask
    rng = np.random.default_rng(1)
    # few distinct values: ties at the threshold in nearly every row
    scores = rng.integers(-3, 4, size=(40, 64)).astype(np.float32)
    scores[0] = 0.0
    scores[1, :5] = [np.inf, -np.inf, -0.0, 0.0, 1e-45]
    valid = rng.random((40, 64)) < 0.8
    valid[2] = False
    valid[3, 5:] = False                       # fewer than k valid
    got = np.asarray(jax.jit(
        lambda s, v: exact_topk_mask(s, v, 10))(scores, valid))
    masked = np.where(valid, scores, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1)
    want = (rank < 10) & valid
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), 10)).all()


def test_select_keys_in_segments_equals_one_segment():
    from mmlspark_tpu.ops import sparse_select
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (48, 4, 16))
    w = jax.random.normal(keys[1], (48, 4))
    k = jax.random.normal(keys[2], (48, 16))
    causal = jnp.arange(48)[:, None] >= jnp.arange(48)[None, :]
    whole = np.asarray(sparse_select.exact_topk_mask(
        sparse_select.index_scores(q, w, k), causal, 8))
    # six segments of 8 queries, the first without a score
    segmented = np.asarray(sparse_select.select_keys(q, w, k, 8, block=4))
    assert (segmented == whole).all()
    # a length that is no multiple of k goes as one segment
    odd = np.asarray(sparse_select.select_keys(q[:44], w[:44], k[:44], 8))
    assert (odd == whole[:44, :44]).all()


def test_selected_attention_kernel_interpreted_matches_the_einsum():
    from mmlspark_tpu.ops.selected_attention import selected_attention
    from mmlspark_tpu.parallel.ring_attention import dense_selected_attention
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    b, h, l, d, dv = 2, 4, 256, 32, 48
    q = jax.random.normal(keys[0], (b, h, l, d), jnp.bfloat16) * 0.3
    k = jax.random.normal(keys[1], (b, h, l, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, l, dv), jnp.bfloat16)
    pos = jnp.arange(l)
    keep = (jax.random.uniform(keys[3], (b, l, l)) < 0.3) \
        & (pos[:, None] >= pos[None, :])
    keep = keep.at[:, 5].set(False)            # a query with no key: 0
    got = selected_attention(q, k, v, keep, interpret=True)
    want = dense_selected_attention(q, k, v, keep)
    assert got.shape == (b, h, l, dv)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=0.03)
    assert not np.asarray(got[:, :, 5].astype(jnp.float32)).any()
