"""Pallas flash attention numerics vs the dense reference.

The kernel must reproduce ring_attention.attention exactly (same online
m/l/o algebra) across causal masking, shard offsets, ragged lengths and
fully-masked rows — interpret mode on CPU."""

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.ops.flash_attention import flash_attention
from mmlspark_tpu.parallel.ring_attention import attention


def _qkv(b, lq, lk, h, d, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    mk = lambda l: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, l, h, d)), dtype)
    return mk(lq), mk(lk), mk(lk)


@pytest.mark.parametrize("lq,lk,causal", [
    (64, 64, False),
    (64, 64, True),
    (100, 100, True),      # ragged: not a block multiple
    (300, 520, False),     # multi-block kv, rectangular
    (520, 300, True),      # multi-block q
])
def test_matches_dense(lq, lk, causal):
    q, k, v = _qkv(2, lq, lk, 3, 16, seed=lq + lk)
    ref = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_shard_offsets_match_dense():
    # causal masking of a sequence shard: global positions via offsets
    q, k, v = _qkv(1, 64, 64, 2, 8, seed=7)
    ref = attention(q, k, v, causal=True, q_offset=64, k_offset=0)
    got = flash_attention(q, k, v, causal=True, q_offset=64, k_offset=0,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fully_masked_rows_zero():
    # keys strictly in the future of every query -> all rows masked;
    # both paths must return zeros, not NaN
    q, k, v = _qkv(1, 32, 32, 2, 8, seed=9)
    ref = attention(q, k, v, causal=True, q_offset=0, k_offset=1000)
    got = flash_attention(q, k, v, causal=True, q_offset=0,
                          k_offset=1000, interpret=True)
    assert np.all(np.asarray(got) == 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref))


def test_gradients_match_dense():
    # the kernel sits in the training path (TransformerBlock), so its
    # custom_vjp backward (dense recompute) must match dense grads
    import jax
    q, k, v = _qkv(1, 48, 48, 2, 8, seed=11)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_bfloat16_inputs():
    q, k, v = _qkv(1, 96, 96, 2, 16, seed=3, dtype=jnp.bfloat16)
    ref = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_wide_head_dim_block_caps():
    """D > 128 halves the v5e block caps (the 1024 blocks overflow the
    16 MB scoped-vmem limit in the backward at D=160); _blocks/_lse_pad
    must agree on the resulting padding, and fwd+bwd must stay correct
    at a wide head dim."""
    import jax
    from mmlspark_tpu.ops.flash_attention import _blocks, _lse_pad

    for d in (64, 128, 160, 256):
        bq, _, pad_q, _ = _blocks(700, 700, d)
        assert _lse_pad(700, d) == 700 + pad_q

    q, k, v = _qkv(1, 300, 300, 2, 160, seed=13)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_dense(q, k, v):
        from mmlspark_tpu.parallel.ring_attention import dense_attention
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


# Interpreted on the CPU a fetch block holds up to 256 rows and a compute
# tile 128 of them, so 256 tokens are the cells' shape class in small:
# one fetch block of 2 x 2 tiles, one of them above the diagonal.
_TILED = {
    "one_block_of_tiles": dict(lq=256, lk=256),
    "two_fetch_blocks": dict(lq=512, lk=512),
    "lq_shorter": dict(lq=256, lk=384, q_offset=128),
    "lq_longer": dict(lq=384, lk=256),
    "not_a_tile_multiple": dict(lq=200, lk=200),
    "keys_end_inside_a_tile": dict(lq=256, lk=300),
    "keys_end_inside_a_tile_non_causal": dict(lq=256, lk=300, causal=False),
    "non_causal": dict(lq=256, lk=256, causal=False),
    "offsets_all_visible": dict(lq=256, lk=256, q_offset=256),
    "offsets_mask_whole_rows": dict(lq=256, lk=256, k_offset=100),
    "offsets_mask_whole_tiles": dict(lq=256, lk=256, k_offset=192),
    "offsets_mask_a_fetch_block": dict(lq=256, lk=512, k_offset=-128),
    "head_128": dict(lq=256, lk=256, d=128),
    "head_160": dict(lq=256, lk=256, d=160),
}


@pytest.mark.parametrize("case", sorted(_TILED))
def test_tiled_matches_dense(case):
    """Forward and dq, dk, dv of the tiled kernels against the dense
    einsum, over what the tile plan adapts to: lengths, head width,
    ``causal`` and the offsets."""
    import jax
    from mmlspark_tpu.parallel.ring_attention import dense_attention
    c = dict(dict(d=64, causal=True, q_offset=0, k_offset=0),
             **_TILED[case])
    q, k, v = _qkv(1, c["lq"], c["lk"], 2, c["d"], seed=len(case))
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                    jnp.float32)
    kw = dict(causal=c["causal"], q_offset=c["q_offset"],
              k_offset=c["k_offset"])

    def loss(fn, **extra):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kw, **extra) * w)

    got, g_got = jax.value_and_grad(
        loss(flash_attention, interpret=True), argnums=(0, 1, 2))(q, k, v)
    ref, g_ref = jax.value_and_grad(
        loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, interpret=True, **kw)),
        np.asarray(dense_attention(q, k, v, **kw)), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-4, atol=1e-3)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("k_offset,dark", [(1000, 256), (192, 192)])
def test_fully_masked_rows_carry_neg_inf_lse(k_offset, dark):
    """Rows with no visible key (whole tiles of them are never visited)
    still return 0 and lse = NEG_INF, which is what the ring layer's
    merge and the backward's recompute read."""
    from mmlspark_tpu.ops.flash_attention import NEG_INF, _flash_forward
    q, k, v = _qkv(1, 256, 256, 2, 64, seed=21)
    out, lse = _flash_forward(q, k, v, True, 0, k_offset, True)
    out, lse = np.asarray(out), np.asarray(lse)
    assert np.all(out[:, :dark] == 0)
    assert np.all(lse[:, :dark] == np.float32(NEG_INF))
    assert np.all(lse[:, dark:] > -1e3) and np.all(np.isfinite(out))


# (lq, lk, d, causal, q_offset, k_offset, block caps or None for the
#  interpreter's[, (tq, tk) in place of the module's tiles])
#  -> tiles run, masked, in the square (None: held to the mask alone)
_PLANS = {
    "cells_shape": ((1024, 1024, 64, True, 0, 0, 1024), (20, 8, 32)),
    "cells_shape_256_tiles": ((1024, 1024, 64, True, 0, 0, 1024, (256, 256)),
                              (10, 4, 16)),
    "cells_shape_non_causal": ((1024, 1024, 64, False, 0, 0, 1024),
                               (32, 0, 32)),
    "padded_keys_non_causal": ((1024, 1000, 64, False, 0, 0, 1024),
                               (32, 8, 32)),
    "block_above_diagonal": ((1024, 1024, 64, True, 0, 2048, 1024),
                             (0, 0, 32)),
    "block_below_diagonal": ((1024, 1024, 64, True, 1024, 0, 1024),
                             (32, 0, 32)),
    "two_fetch_blocks": ((2048, 2048, 64, True, 0, 0, 1024), (72, 16, 128)),
    "wide_head_halves_blocks": ((1024, 1024, 160, True, 0, 0, 512),
                                (20, 8, 32)),
    "ragged_keys": ((1100, 1100, 64, True, 0, 0, 1024), None),
    "block_not_a_tile_multiple": ((704, 704, 64, True, 0, 0, 1024), None),
    "offsets_inside_a_tile": ((1024, 2048, 64, True, 700, 100, 1024), None),
    "interpreter_blocks": ((520, 300, 16, True, 0, 0, None), None),
    "interpreter_offsets": ((256, 512, 64, True, 0, -128, None), None),
    "interpreter_short": ((100, 100, 16, True, 0, 0, None), (1, 1, 1)),
}


def _check_stretch(fa, plan, kind, allowed, qi, ki, r, nr, c, nc, masked):
    """A stretch the plan calls bare is all allowed, and a masked
    stretch's mask is the dense one's."""
    if not nr or not nc:
        return
    r0, c0 = qi * plan.bq + r, ki * plan.bk + c
    dense = allowed[r0:r0 + nr, c0:c0 + nc]
    if masked:
        np.testing.assert_array_equal(
            np.asarray(fa._valid_mask(plan, kind, r, nr, c, nc)), dense)
    else:
        assert dense.all()


@pytest.mark.parametrize("case", sorted(_PLANS))
def test_tile_plan_counts(monkeypatch, case):
    """The plan is the mechanism's counter, and the only place that
    knows which tiles run: its run tiles must cover exactly the pairs
    ``_valid_mask`` allows, so a plan that skips a needed tile (or
    drops the mask from a tile that needs it) fails here and not on
    the chip."""
    from mmlspark_tpu.ops import flash_attention as fa
    (lq, lk, d, causal, q_offset, k_offset, cap, *tiles), want = _PLANS[case]
    if cap:
        monkeypatch.setattr(fa, "_block_caps", lambda d: (cap, cap))
    plan = fa.tile_plan(lq, lk, d, causal, q_offset, k_offset)
    if tiles:
        import dataclasses
        plan = dataclasses.replace(plan, tq=tiles[0][0], tk=tiles[0][1])
    (nq, nk), (ni, nj) = plan.grid, plan.tiles
    assert (plan.bq, plan.bk) == fa._blocks(lq, lk, d)[:2]

    rows = np.arange(nq * plan.bq)[:, None]
    cols = np.arange(nk * plan.bk)[None, :]
    allowed = np.broadcast_to(cols < lk, (rows.size, cols.size))
    if causal:
        allowed = allowed & (rows + q_offset >= cols + k_offset)
    seen = {"bare": 0, "masked": 0, "skipped": 0}
    for qi in range(nq):
        for ki in range(nk):
            kind = plan.block_kind(qi, ki)
            assert kind in plan.kinds()
            for i in range(ni):
                for j in range(nj):
                    r0 = qi * plan.bq + i * plan.tq
                    c0 = ki * plan.bk + j * plan.tk
                    tile = allowed[r0:r0 + plan.tq, c0:c0 + plan.tk]
                    got = plan.tile_kind(kind, i, j)
                    assert got == ("bare" if tile.all() else "masked"
                                   if tile.any() else "skipped"), \
                        (qi, ki, i, j)
                    # the dk/dv kernel walks the same tiles by key
                    first, bare = plan.query_span(kind, j)
                    assert got == ("skipped" if i < first else "masked"
                                   if i < bare else "bare"), (qi, ki, i, j)
                    seen[got] += 1
                # the forward and dq walk a query tile's keys in one
                # stretch and mask it from its first masked tile on
                bare, run = plan.key_span(kind, i)
                _check_stretch(fa, plan, kind, allowed, qi, ki, i * plan.tq,
                               plan.tq, 0, bare * plan.tk, False)
                _check_stretch(fa, plan, kind, allowed, qi, ki, i * plan.tq,
                               plan.tq, bare * plan.tk,
                               (run - bare) * plan.tk, True)
            for j in range(nj):
                # dk/dv walks a key tile's queries: masked, then bare
                first, bare = plan.query_span(kind, j)
                _check_stretch(fa, plan, kind, allowed, qi, ki,
                               first * plan.tq, (bare - first) * plan.tq,
                               j * plan.tk, plan.tk, True)
                _check_stretch(fa, plan, kind, allowed, qi, ki,
                               bare * plan.tq, (ni - bare) * plan.tq,
                               j * plan.tk, plan.tk, False)
            assert plan.runs(kind) == allowed[
                qi * plan.bq:(qi + 1) * plan.bq,
                ki * plan.bk:(ki + 1) * plan.bk].any()
    counts = plan.counts()
    blocks = allowed.reshape(nq, plan.bq, nk, plan.bk).any((1, 3))
    assert counts == {"tiles_square": sum(seen.values()),
                      "tiles_run": seen["bare"] + seen["masked"],
                      "tiles_masked": seen["masked"],
                      # without a window the grid steps over every block
                      "blocks_run": int(blocks.sum()),
                      "blocks_grid": nq * nk}
    if want:
        assert (counts["tiles_run"], counts["tiles_masked"],
                counts["tiles_square"]) == want


@pytest.mark.parametrize("batch", [8, 3])   # 3 does not divide: replicated
def test_flash_per_shard_under_a_mesh(cpu_mesh_devices, batch):
    """XLA cannot partition a Mosaic kernel, so under a multi-device jit
    ``attention`` runs it per shard (batch over the data axis). Values
    and gradients must equal the unsharded kernel's."""
    import functools

    import jax
    from mmlspark_tpu.parallel import mesh as mesh_lib
    from mmlspark_tpu.parallel.ring_attention import flash_per_shard

    mesh = mesh_lib.make_mesh({"data": 4, "fsdp": 2})
    q, k, v = _qkv(batch, 40, 40, 2, 8, seed=17)
    flash = functools.partial(flash_attention, causal=True, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    with jax.set_mesh(mesh):
        sharded = functools.partial(
            flash_per_shard, flash, jax.sharding.get_abstract_mesh())
        got = jax.jit(jax.value_and_grad(loss(sharded), argnums=(0, 1, 2))
                      )(q, k, v)
    ref = jax.value_and_grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
