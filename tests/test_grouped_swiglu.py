"""The first half of the routed experts' feed-forward as one grouped
kernel (``ops/grouped_matmul.grouped_swiglu``): the Pallas kernel in
interpreter mode and the path taken off the chip, both against
``silu(ragged_dot) * ragged_dot`` in float32 cast once, at small shapes:
a group boundary inside a row tile, an empty group, a group spanning
several tiles, a last pass not full (its rows past the pairs are never
read), several n tiles, and the write landing in slice ``lo`` of the
buffer with every other slice left as it was."""

import functools

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


def _in_order(x, tok):
    return jnp.arange(x.shape[0], dtype=jnp.int32) if tok is None else tok


def _kernel(tiles):
    """The kernel at these tiles, interpreted, reading the rows of
    ``x`` through ``tok`` (in order where none are given) from their
    table."""
    def run(x, w_gate, w_up, group_sizes, into, lo, tok=None):
        return gm._grouped_swiglu(gm._table(x), _in_order(x, tok), w_gate,
                                  w_up, group_sizes, into, lo, tiles=tiles,
                                  interpret=True)
    return run


def _off_the_chip(x, w_gate, w_up, group_sizes, into, lo, tok=None):
    tok = _in_order(x, tok)
    return gm.grouped_swiglu(gm.row_table(x, tok.shape[0]), w_gate, w_up,
                             group_sizes, into, lo, tok=tok)


FORMS = {**{name: _kernel(tiles) for name, tiles in TILES.items()},
         "off_the_chip": _off_the_chip}


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


# the pass's token ids, and the sizes each is read with: the rows of a
# table of TOKENS tokens that ``tok`` names, against the same rows
# gathered first (``grouped_swiglu(u[tok], ...)``)
TOKENS = 40
IDS = {
    # every token once, shuffled; a tile two groups share is visited
    # twice and its second visit finds its rows in the slot
    "permuted": "boundary_inside_a_tile",
    # each token in k = 2 groups, as ``routed_experts`` sorts its pairs
    "repeated_across_groups": "a_group_over_several_tiles",
    # ids past the pairs are token 0's (``token_of``'s padding), in a
    # last pass not full
    "padded_past_the_pairs": "last_pass_not_full",
    # an empty group between two others
    "repeated_with_an_empty_group": "an_empty_group",
}


def _ids(kind, sizes, seed=5):
    rng = np.random.default_rng(seed)
    if kind == "permuted":
        tok = np.concatenate([rng.permutation(TOKENS),
                              rng.permutation(TOKENS)[:M - TOKENS]])
    else:
        # token t's two pairs, sorted by expert as a stable sort of a
        # random choice would leave them
        chosen = np.stack([rng.choice(GROUPS, 2, replace=False)
                           for _ in range(M // 2)])
        tok = (np.argsort(chosen.reshape(-1), kind="stable") // 2) % TOKENS
    tok = tok.astype(np.int32)
    if kind == "padded_past_the_pairs":
        tok[sum(sizes):] = 0
    return jnp.asarray(tok)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("kind", IDS)
def test_rows_read_through_ids_equal_the_gathered_rows_to_the_bit(
        kind, form, dtype):
    """The same kernel, its rows read through their ids from the table
    or handed in gathered: the same rows reach the same products in the
    same tiles, so the buffer is the same to the bit, and every slice
    but the pass's is as it was."""
    dtype = jnp.dtype(dtype)
    x, w_gate, w_up = _operands(dtype, seed=4)
    u = x[:TOKENS]
    sizes = SIZES[IDS[kind]]
    tok = _ids(kind, sizes)
    filled = jnp.full((PASSES * M, N), 7.0, dtype)
    run = functools.partial(FORMS[form], w_gate=w_gate, w_up=w_up,
                            group_sizes=jnp.asarray(sizes, jnp.int32),
                            into=filled, lo=jnp.int32(M))
    got = np.asarray(run(u, tok=tok).astype(jnp.float32))
    want = np.asarray(run(u[tok]).astype(jnp.float32))
    assert np.array_equal(got, want)
    pairs = sum(sizes)
    assert np.isfinite(got[M:M + pairs]).all()
    assert np.abs(got[M:M + pairs]).max() > 0
    assert (got[:M] == 7.0).all() and (got[2 * M:] == 7.0).all()


@pytest.mark.parametrize("tiles", [(16, 256), (16, 128)],
                         ids=["one_n_tile", "two_n_tiles"])
def test_the_copies_a_visit_ahead_are_waited_for(tiles):
    """Under the TPU interpreter a copy runs as on the chip: started,
    in flight, done only when waited for, and a read of a slot with a
    copy in flight is a race. Visits that start the next visit's rows
    into the other slot and wait for their own give the gathered rows'
    buffer to the bit, and no race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    x, w_gate, w_up = _operands(jnp.bfloat16, seed=7)
    u = x[:TOKENS]
    sizes = SIZES["boundary_inside_a_tile"]
    tok = _ids("permuted", sizes)
    args = (w_gate, w_up, jnp.asarray(sizes, jnp.int32),
            jnp.full((PASSES * M, N), 7.0, jnp.bfloat16), jnp.int32(M))
    got = gm._grouped_swiglu(
        gm._table(u), tok, *args, tiles=tiles,
        interpret=pltpu.InterpretParams(detect_races=True))
    assert not interpret_pallas_call.races.races_found
    want = _kernel(tiles)(u[tok], *args)
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))


def test_the_ids_cover_what_they_are_for():
    for kind, case in IDS.items():
        tok = np.asarray(_ids(kind, SIZES[case]))
        assert tok.min() >= 0 and tok.max() < TOKENS
    # a token repeated across groups
    tok = np.asarray(_ids("repeated_across_groups",
                          SIZES["a_group_over_several_tiles"]))
    assert np.bincount(tok).max() > 1
    tok = np.asarray(_ids("padded_past_the_pairs",
                          SIZES["last_pass_not_full"]))
    assert (tok[37:] == 0).all()
    # the permuted ids reach across several row tiles of 16
    tok = np.asarray(_ids("permuted", SIZES["boundary_inside_a_tile"]))
    assert sorted(tok[:TOKENS]) == list(range(TOKENS))


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
                 for f in (FORMS[form], FORMS["off_the_chip"]))
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
    jaxpr = jax.make_jaxpr(gm.grouped_swiglu)(
        x, w_gate, w_up, sizes, into, jnp.int32(M),
        tok=jnp.arange(M, dtype=jnp.int32))
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


@pytest.mark.parametrize("form", FORMS)
def test_rows_keep_their_own_dtype(form):
    """float32 rows against bfloat16 weights: the rows reach the
    products as they are, on every path (rounded to the weights'
    dtype they would miss the float32 reference by ~1e-3)."""
    x, _, _ = _operands(jnp.float32, seed=8)
    _, w_gate, w_up = _operands(jnp.bfloat16, seed=8)
    sizes = SIZES["boundary_inside_a_tile"]
    got = FORMS[form](x, w_gate, w_up, jnp.asarray(sizes, jnp.int32),
                      jnp.zeros((M, N), jnp.float32), jnp.int32(0))
    want = _reference(x, w_gate, w_up, sizes, jnp.float32)
    assert np.linalg.norm(np.asarray(got) - want) \
        <= 1e-5 * np.linalg.norm(want)


@pytest.mark.parametrize("dtype,k", [("bfloat16", 32), ("bfloat16", 33),
                                     ("bfloat16", 2304), ("float32", 24)])
def test_a_row_comes_back_from_its_table_to_the_bit(dtype, k):
    """``_table``'s 32-bit words, put side by side again by ``_rows`` (as
    the kernel does in VMEM), are the row: a bfloat16 row in half as many
    words (at an odd k too, whose last word has no high half), a float32
    row as it is."""
    dtype = jnp.dtype(dtype)
    x = jnp.asarray(np.random.default_rng(k).standard_normal((5, k)),
                    dtype).at[0, :4].set(
        jnp.asarray([0.0, -0.0, np.inf, -1e-40], dtype))
    words = gm._table(x)
    width = -(-k // 2) if dtype == jnp.bfloat16 else k
    assert words.shape == (5, 1, width)
    assert words.dtype == (jnp.uint32 if dtype == jnp.bfloat16 else dtype)
    back = gm._rows(words[:, 0, :], dtype, k)
    assert back.dtype == dtype
    bits = jnp.uint16 if dtype == jnp.bfloat16 else jnp.uint32
    assert np.array_equal(np.asarray(lax.bitcast_convert_type(back, bits)),
                          np.asarray(lax.bitcast_convert_type(x, bits)))


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
    u = x[:TOKENS]
    tok = _ids("repeated_across_groups", SIZES["tiles_whole"])
    into = jnp.zeros((PASSES * M, N), jnp.bfloat16)
    sizes = jnp.asarray(SIZES["tiles_whole"], jnp.int32)

    def through_ids(u, *rest):
        return gm.grouped_swiglu(gm.row_table(u, M), *rest, tok=tok)
    jaxpr = jax.make_jaxpr(through_ids)(u, w_gate, w_up, sizes, into,
                                        jnp.int32(M)).jaxpr
    values = values_outside_kernels(jaxpr)
    # outside the kernel no float32 (rows, width) value exists, and
    # the pass's rows are never gathered: no (rows, k) value, no gather
    assert ((M, N), jnp.float32) not in values
    assert not [shape for shape, _ in values if shape == (M, K)]
    assert not [e for e in _walk(jaxpr) if e.primitive.name == "gather"
                and e.invars[0].aval.shape[0] == TOKENS]
    # the table the rows come from: the tokens' bfloat16 rows as half
    # as many 32-bit words, a row to a unit axis of its own
    assert ((TOKENS, 1, K // 2), jnp.uint32) in values
    assert not [shape for shape, dtype in values
                if dtype == jnp.float32 and TOKENS in shape]
    # (a function of its own: a trace of ``grouped_swiglu`` itself may
    # be remembered from the test above)
    text = str(jax.make_jaxpr(lambda *a: through_ids(*a))(
        u, w_gate, w_up, sizes, into, jnp.int32(M)))
    assert text.count("pallas_call") == 1
    assert "ragged_dot" not in text
    # the buffer goes in aliased to the output, after the five
    # scalar-prefetch operands (the metadata's three, the first block,
    # the ids) and the table and the two weights
    assert "input_output_aliases=((8, 0),)" in text


def _primitives(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, found)
    return found


def test_sorted_rows_on_the_chip_are_read_through_ids_in_order(monkeypatch):
    """Rows already sorted are read through ids 0, 1, ... from their
    own table: one kernel, the same one as for rows read through a
    pass's token ids."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, w_gate, w_up = _operands(jnp.float32, seed=6)
    into = jnp.zeros((PASSES * M, N), jnp.float32)
    sizes = jnp.asarray(SIZES["boundary_inside_a_tile"], jnp.int32)
    text = str(jax.make_jaxpr(lambda x, *a: gm.grouped_swiglu(
        gm.row_table(x, M), *a, tok=jnp.arange(M, dtype=jnp.int32)))(
        x, w_gate, w_up, sizes, into, jnp.int32(0)))
    assert text.count("pallas_call") == 1 and "iota" in text


@pytest.mark.parametrize("tiles", [(16, 256), (64, 128)])
def test_the_kernel_body_is_the_same_size_at_any_row_tile(tiles):
    """The copies of a visit's rows are started from a rolled loop of a
    few copies a trip: the kernel's body does not grow with its rows."""
    x, w_gate, w_up = _operands(jnp.bfloat16)
    sizes = jnp.asarray(SIZES["tiles_whole"], jnp.int32)
    into = jnp.zeros((PASSES * M, N), jnp.bfloat16)

    def kernel_eqns(tiles):
        jaxpr = jax.make_jaxpr(lambda *a: gm._grouped_swiglu(
            *a, tiles=tiles, interpret=True))(
            gm._table(x), jnp.arange(M, dtype=jnp.int32), w_gate, w_up,
            sizes, into, jnp.int32(0)).jaxpr
        call, = [e for e in _walk(jaxpr) if e.primitive.name == "pallas_call"]
        return len(_primitives(call.params["jaxpr"]))
    assert kernel_eqns(tiles) == kernel_eqns((8, 256))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)
