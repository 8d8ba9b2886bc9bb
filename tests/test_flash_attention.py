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
