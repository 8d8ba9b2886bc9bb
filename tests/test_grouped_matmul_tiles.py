"""The grouped product's tile follows the shape: a k or n side that is
no multiple of the 1024 tile (LFM2's experts are 1536 wide) gets the
widest multiple of 128 that divides it; GLM's sides keep their 1024."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import grouped_matmul as gm


@pytest.mark.parametrize("side,tile", [
    (1536, 768), (2048, 1024), (6144, 1024), (12288, 1024),
    (1024, 1024), (64, 64), (32, 32), (3072, 1024), (2560, 640),
    (1000, 1000), (3000, 1024)])
def test_tile_divides_the_side_where_anything_does(side, tile):
    got = gm._tile(side, 1024)
    assert got == tile
    if side % 128 == 0:
        assert side % got == 0 and got <= 1024


def test_tiling_of_both_cells_products():
    assert gm.TILING == (512, 1024, 1024)
    # (k, n) of gate/up and of down: LFM2 (2048 -> 1536 -> 2048)
    assert [(gm._tile(k, 1024), gm._tile(n, 1024))
            for k, n in ((2048, 1536), (1536, 2048))] == [
        (1024, 768), (768, 1024)]
    # ... and GLM (6144 -> 2048 -> 6144), as they were
    assert [(gm._tile(k, 1024), gm._tile(n, 1024))
            for k, n in ((6144, 2048), (2048, 6144))] == [
        (1024, 1024), (1024, 1024)]


def test_grouped_matmul_off_the_chip_is_the_ragged_product():
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(keys[0], (24, 16))
    rhs = jax.random.normal(keys[1], (3, 16, 12))
    sizes = jnp.asarray([5, 0, 9])
    got = gm.grouped_matmul(lhs, rhs, sizes, jnp.float32)
    want = np.zeros((24, 12), np.float32)
    want[:5] = lhs[:5] @ rhs[0]
    want[5:14] = lhs[5:14] @ rhs[2]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
