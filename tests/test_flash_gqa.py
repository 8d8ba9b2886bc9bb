"""Grouped-query attention through the flash kernels, interpreted on the
CPU: H query heads over H_kv key/value heads without a repeated copy of
K and V, forward and backward held to ``dense_attention`` on repeated K
and V at lengths that span several fetch blocks (256 rows a block
off-TPU); H_kv == H is the call as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import flash_attention as fa
from mmlspark_tpu.parallel.ring_attention import dense_attention


def _qkv(b, lq, lk, h, hk, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, lq, h, d)),
            jax.random.normal(ks[1], (b, lk, hk, d)),
            jax.random.normal(ks[2], (b, lk, hk, d)),
            jax.random.normal(ks[3], (b, lq, h, d)))


@pytest.mark.parametrize("lq,lk,h,hk,causal", [
    (640, 640, 8, 2, True),       # 3 x 3 fetch blocks, the last ragged
    (768, 768, 4, 1, True),       # multi-query: one key/value head
    (512, 512, 6, 3, True),
    (300, 520, 8, 4, False),      # cross lengths, no mask
    (64, 64, 4, 2, True),         # one small block
])
def test_forward_matches_dense_on_repeated_kv(lq, lk, h, hk, causal):
    q, k, v, _ = _qkv(2, lq, lk, h, hk, 32)
    got = fa.flash_attention(q, k, v, causal=causal, interpret=True)
    rep = h // hk
    want = dense_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                           causal)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the einsum path repeats the shared head itself
    np.testing.assert_allclose(dense_attention(q, k, v, causal), want,
                               rtol=1e-6, atol=1e-6)


def test_key_value_head_is_h_floordiv_group_not_h_mod():
    q, k, v, _ = _qkv(1, 256, 256, 8, 2, 32, seed=1)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    serves = np.arange(8) % 2
    wrong = dense_attention(q, k[:, :, serves], v[:, :, serves], True)
    assert np.abs(np.asarray(got - wrong)).max() > 0.05


@pytest.mark.parametrize("l,h,hk", [(640, 8, 2), (384, 4, 1)])
def test_backward_matches_dense_on_repeated_kv(l, h, hk):
    q, k, v, g = _qkv(2, l, l, h, hk, 32, seed=2)
    rep = h // hk

    def flash_loss(q, k, v):
        return (fa.flash_attention(q, k, v, causal=True, interpret=True)
                * g).sum()

    def dense_loss(q, k, v):
        return (dense_attention(q, jnp.repeat(k, rep, 2),
                                jnp.repeat(v, rep, 2), True) * g).sum()
    got = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                   err_msg=name)


def test_bfloat16_grouped_backward_adds_the_group_in_float32():
    q, k, v, g = _qkv(1, 256, 256, 8, 2, 64, seed=3)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    dq, dk, dv = jax.grad(lambda q, k, v: (fa.flash_attention(
        q, k, v, causal=True, interpret=True).astype(jnp.float32)
        * g).sum(), (0, 1, 2))(q, k, v)
    assert dk.dtype == dv.dtype == jnp.bfloat16 and dk.shape == k.shape
    want = jax.grad(lambda q, k, v: (dense_attention(
        q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2), True) * g).sum(),
        (1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    for got, ref in zip((dk, dv), want):
        assert np.linalg.norm(got.astype(jnp.float32) - ref) \
            < 0.02 * np.linalg.norm(ref)


@pytest.mark.parametrize("hk", [3, 5])
def test_heads_that_make_no_whole_group_are_refused(hk):
    q, k, v, _ = _qkv(1, 64, 64, 8, hk, 32)
    with pytest.raises(ValueError, match="divisor"):
        fa.flash_attention(q, k, v, causal=True, interpret=True)
    with pytest.raises(ValueError, match="same number"):
        fa.flash_attention(q, k[:, :, :1], v, causal=True, interpret=True)


@pytest.mark.parametrize("key_axis", [1, 2])
def test_equal_heads_read_k_and_v_as_they_always_did(key_axis):
    """H_kv == H is the parent's call: its K/V index map is the query
    head's own index with no arithmetic in it (the whole call's jaxpr,
    forward and backward, was compared with the parent commit's to the
    character when this was written: PERF.md, PR 34); a group divides."""
    args = (5, 1, 2)
    plain = fa._kv_block(256, 64, 1, key_axis)
    shared = fa._kv_block(256, 64, 4, key_axis)
    assert plain.block_shape == shared.block_shape == (1, 256, 64)
    assert not jax.make_jaxpr(plain.index_map)(*args).eqns
    assert len(jax.make_jaxpr(shared.index_map)(*args).eqns) >= 1
    block = args[key_axis]
    assert plain.index_map(*args) == (5, block, 0)
    assert shared.index_map(*args) == (1, block, 0)
    q, k, v, _ = _qkv(1, 64, 64, 4, 4, 32)
    assert fa._kv_group(q, k, v) == 1
    assert fa._kv_group(q, k[:, :, :2], v[:, :, :2]) == 2
