"""The first half of the routed experts' feed-forward as one grouped
kernel (``ops/grouped_matmul.grouped_swiglu``): the Pallas kernel in
interpreter mode and the path taken off the chip, both against
``silu(ragged_dot) * ragged_dot`` in float32 cast once, at small shapes:
a group boundary inside a row tile, an empty group, a group spanning
several tiles, a last pass not full (its rows past the pairs are never
read), several n tiles, and the write landing in slice ``lo`` of the
buffer with every other slice left as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mmlspark_tpu.ops import grouped_matmul as gm

M, K, N, GROUPS, PASSES = 64, 32, 256, 4, 3
# group sizes of one pass of M rows in row tiles of 16
SIZES = {
    "boundary_inside_a_tile": [20, 10, 30, 4],
    "an_empty_group": [16, 0, 40, 8],
    "a_group_over_several_tiles": [3, 55, 0, 6],
    "tiles_whole": [16, 16, 16, 16],
    "last_pass_not_full": [20, 0, 17, 0],
    "one_group": [0, 0, 64, 0],
}
# (row tile, n tile)
TILES = {"one_n_tile": (16, 256), "two_n_tiles": (16, 128),
         "wider_rows": (32, 256), "one_row_tile": (64, 256)}


def _operands(dtype, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), dtype)
    return (draw(M, K), draw(GROUPS, K, N) * K ** -0.5,
            draw(GROUPS, K, N) * K ** -0.5)


def _reference(x, w_gate, w_up, sizes, dtype):
    """silu(x @ w_gate[g]) * (x @ w_up[g]) a group, in float32 from the
    operands as they are, cast once; rows of no group left out."""
    f32 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    rows, lo = [], 0
    for g, size in enumerate(sizes):
        a = f32(x)[lo:lo + size] @ f32(w_gate)[g]
        rows.append(a / (1.0 + np.exp(-a)) * (f32(x)[lo:lo + size]
                                              @ f32(w_up)[g]))
        lo += size
    return np.asarray(jnp.asarray(np.concatenate(rows), jnp.float32
                                  ).astype(dtype).astype(jnp.float32))


def _kernel(tiles):
    return lambda *a: gm._grouped_swiglu(*a, tiles=tiles, interpret=True)


FORMS = {**{name: _kernel(tiles) for name, tiles in TILES.items()},
         "off_the_chip": gm.grouped_swiglu}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", SIZES)
def test_rows_of_a_group_against_the_float32_reference(case, form, dtype):
    dtype = jnp.dtype(dtype)
    x, w_gate, w_up = _operands(dtype)
    sizes = SIZES[case]
    pairs = sum(sizes)
    # rows past the pairs are never read: whatever they hold (NaN here)
    # reaches no row of a group
    x = x.at[pairs:].set(jnp.nan)
    filled = jnp.full((PASSES * M, N), 7.0, dtype)
    lo = M          # the middle slice
    got = np.asarray(FORMS[form](
        x, w_gate, w_up, jnp.asarray(sizes, jnp.int32), filled,
        jnp.int32(lo)).astype(jnp.float32))
    want = _reference(x, w_gate, w_up, sizes, dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert np.isfinite(got[lo:lo + pairs]).all()
    assert np.linalg.norm(got[lo:lo + pairs] - want) \
        <= tol * np.linalg.norm(want)
    # every other slice of the buffer is as it was
    assert (got[:lo] == 7.0).all() and (got[lo + M:] == 7.0).all()


@pytest.mark.parametrize("form", TILES)
def test_the_kernel_rounds_as_the_path_off_the_chip(form):
    """bfloat16 operands, float32 sums, silu and product in float32,
    one cast: the kernel's rows equal two ``ragged_dot`` and the
    element-wise operations to bfloat16's last place."""
    x, w_gate, w_up = _operands(jnp.bfloat16, seed=1)
    sizes = jnp.asarray(SIZES["boundary_inside_a_tile"], jnp.int32)
    into = jnp.zeros((M, N), jnp.bfloat16)
    got, want = (np.asarray(f(x, w_gate, w_up, sizes, into, jnp.int32(0)
                              ).astype(jnp.float32))
                 for f in (FORMS[form], gm.grouped_swiglu))
    # the sums' order differs (the interpreter's dot against
    # ragged_dot's): a unit in the last place now and then, no more
    assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()
    assert (got != want).mean() < 0.02


@pytest.mark.parametrize("form", FORMS)
def test_the_one_cast_is_to_the_buffer_s_dtype(form):
    """bfloat16 operands into a float32 buffer: the float32 product of
    silu and up as it stands (what ``chip_smoke.py`` sets beside
    ``grouped_rel_l2``)."""
    x, w_gate, w_up = _operands(jnp.bfloat16, seed=3)
    sizes = SIZES["boundary_inside_a_tile"]
    got = FORMS[form](x, w_gate, w_up, jnp.asarray(sizes, jnp.int32),
                      jnp.zeros((M, N), jnp.float32), jnp.int32(0))
    assert got.dtype == jnp.float32
    want = _reference(x, w_gate, w_up, sizes, jnp.float32)
    assert np.linalg.norm(np.asarray(got) - want) \
        <= 1e-5 * np.linalg.norm(want)


@pytest.mark.parametrize("form", TILES)
def test_a_row_past_the_pairs_is_left_as_found_or_unread(form):
    """A last pass not full: tiles wholly past the pairs are not
    visited (their rows of the buffer keep what they held), and a row
    past the pairs inside a visited tile may hold anything: nothing the
    caller reads."""
    x, w_gate, w_up = _operands(jnp.float32)
    sizes = SIZES["last_pass_not_full"]         # 37 of 64 rows
    tm = TILES[form][0]
    visited = -(-sum(sizes) // tm) * tm
    into = jnp.full((PASSES * M, N), 7.0, jnp.float32)
    got = np.asarray(FORMS[form](x, w_gate, w_up,
                                 jnp.asarray(sizes, jnp.int32), into,
                                 jnp.int32(2 * M)))
    assert (got[:2 * M] == 7.0).all()
    assert (got[2 * M + visited:] == 7.0).all()


def test_the_loop_of_passes_fills_the_buffer_slice_by_slice():
    """As ``routed_experts`` runs it: a ``fori_loop`` whose carry is the
    buffer, aliased to the kernel's output, one slice a trip."""
    x, w_gate, w_up = _operands(jnp.float32, seed=2)
    by_pass = jnp.asarray([SIZES["boundary_inside_a_tile"],
                           SIZES["an_empty_group"],
                           SIZES["tiles_whole"]], jnp.int32)

    def filled(form):
        def one_pass(i, acc):
            return form(x * (i + 1), w_gate, w_up, by_pass[i], acc, i * M)
        return jax.jit(lambda: lax.fori_loop(
            0, PASSES, one_pass, lax.empty((PASSES * M, N), jnp.float32)))()
    got, want = (np.asarray(filled(FORMS[f]))
                 for f in ("two_n_tiles", "off_the_chip"))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.abs(want[M:2 * M]).max() > np.abs(want[:M]).max()


def test_off_the_chip_is_two_ragged_products_and_an_update_slice():
    x, w_gate, w_up = _operands(jnp.bfloat16)
    into = jnp.zeros((PASSES * M, N), jnp.bfloat16)
    sizes = jnp.asarray(SIZES["tiles_whole"], jnp.int32)
    jaxpr = jax.make_jaxpr(gm.grouped_swiglu)(x, w_gate, w_up, sizes, into,
                                              jnp.int32(M))
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims.count("ragged_dot_general") == 2
    assert prims[-1] == "dynamic_update_slice"
    assert "pallas_call" not in prims
    # float32 until the one cast
    casts = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "convert_element_type"
             and e.outvars[0].aval.shape == (M, N)]
    assert [(c.invars[0].aval.dtype, c.outvars[0].aval.dtype)
            for c in casts] == [(jnp.float32, jnp.bfloat16)]


@pytest.mark.parametrize("m,k,n,itemsize,tiles", [
    # the LFM2 and the Mellum2 cell's pass: all of n, x read once
    (32768, 2048, 1536, 2, (256, 1536)),
    (32768, 2304, 896, 2, (256, 896)),
    # GLM's widths, were they ever to come here: two n tiles of 1024
    (20480, 6144, 2048, 2, (256, 1024)),
    # float32 weights take twice the room
    (4096, 6144, 2048, 4, (256, 512)),
    # small and odd sides: one tile each
    (64, 32, 24, 4, (64, 24)), (8, 16, 1000, 2, (8, 1000))])
def test_the_tile_plan_follows_the_shape(m, k, n, itemsize, tiles):
    got = gm._swiglu_tiles(m, k, n, itemsize)
    assert got == tiles
    tm, tn = got
    assert m % tm == 0 and n % tn == 0
    assert 4 * k * tn * itemsize <= gm.SWIGLU_WEIGHT_BYTES


def values_outside_kernels(jaxpr, found=None):
    """(shape, dtype) of every value a jaxpr's equations produce, the
    nested jaxprs' too, a Pallas kernel's own (they live in VMEM) left
    out."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found += [(v.aval.shape, v.aval.dtype) for v in eqn.outvars]
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                values_outside_kernels(sub, found)
    return found


def test_on_the_chip_it_is_one_pallas_call(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, w_gate, w_up = _operands(jnp.bfloat16)
    into = jnp.zeros((PASSES * M, N), jnp.bfloat16)
    sizes = jnp.asarray(SIZES["tiles_whole"], jnp.int32)
    # outside the kernel no float32 (rows, width) value exists
    assert ((M, N), jnp.float32) not in values_outside_kernels(
        jax.make_jaxpr(lambda *a: gm.grouped_swiglu(*a))(
            x, w_gate, w_up, sizes, into, jnp.int32(M)).jaxpr)
    # (a function of its own: a trace of ``grouped_swiglu`` itself may
    # be remembered from the test above)
    text = str(jax.make_jaxpr(lambda *a: gm.grouped_swiglu(*a))(
        x, w_gate, w_up, sizes, into, jnp.int32(M)))
    assert text.count("pallas_call") == 1
    assert "ragged_dot" not in text
    # the buffer goes in aliased to the output, after the four
    # scalar-prefetch operands and x and the two weights
    assert "input_output_aliases=((7, 0),)" in text
