"""A sliding window through the flash forward, interpreted on the CPU:
query p sees keys j with ``0 <= p - j < window``. The forward against
``dense_attention`` with the positional mask at lengths that span
several fetch blocks (256 rows a block off-TPU), windows smaller than,
equal to and larger than a fetch block and not multiples of a tile, with
fewer key/value heads than query heads; ``TilePlan``'s ``tile_kind`` of
every tile, its band and its counts held to a dense boolean mask; the
backward of a windowed call refused; and a call without a window
traced to the jaxpr it had before the window existed."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import flash_attention as fa
from mmlspark_tpu.parallel.ring_attention import attention, dense_attention


def _qkv(b, lq, lk, h, hk, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, lq, h, d)),
            jax.random.normal(ks[1], (b, lk, hk, d)),
            jax.random.normal(ks[2], (b, lk, hk, d)))


# (length, window, query heads, key/value heads)
_CALLS = [
    (640, 256, 4, 2),       # the window is one fetch block; ragged length
    (640, 100, 4, 2),       # smaller than a block, no multiple of a tile
    (768, 300, 8, 1),       # larger than a block; multi-query
    (1100, 257, 2, 2),      # one key over a block, five blocks a side
    (520, 1000, 4, 2),      # larger than the row: the causal call's result
    (900, 64, 2, 1),        # a window inside one tile
    (512, 1, 2, 2),         # a query sees itself alone
]


@pytest.mark.parametrize("length,window,h,hk", _CALLS)
def test_windowed_forward_matches_dense_with_the_positional_mask(
        length, window, h, hk):
    q, k, v = _qkv(2, length, length, h, hk, 32, seed=length)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True,
                             window=window)
    want = dense_attention(q, k, v, True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # ... whose mask is the positions', written out
    pos = np.arange(length)
    ago = pos[:, None] - pos[None, :]
    seen = (ago >= 0) & (ago < window)
    rep = h // hk
    s = np.einsum("bqhd,bkhd->bhqk", q, np.repeat(k, rep, 2)) / np.sqrt(32)
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    plain = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True),
                      np.repeat(v, rep, 2))
    np.testing.assert_allclose(want, plain, rtol=2e-4, atol=2e-5)
    if window >= length:
        np.testing.assert_allclose(
            got, fa.flash_attention(q, k, v, causal=True, interpret=True),
            rtol=1e-6, atol=1e-6)


def test_a_window_counts_the_query_itself():
    q, k, v = _qkv(1, 300, 300, 2, 2, 16, seed=3)
    alone = fa.flash_attention(q, k, v, causal=True, interpret=True,
                               window=1)
    np.testing.assert_allclose(alone, v, rtol=1e-6, atol=1e-6)


def test_windowed_shard_with_offsets():
    """Queries 512.. of a longer row against keys 256..: the offsets
    place the band."""
    q, k, v = _qkv(1, 300, 556, 4, 2, 32, seed=4)
    kw = dict(causal=True, q_offset=512, k_offset=256)
    got = fa.flash_attention(q, k, v, interpret=True, window=200, **kw)
    want = dense_attention(q, k, v, window=200, **kw)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_dispatcher_hands_the_window_on():
    q, k, v = _qkv(1, 96, 96, 4, 2, 16, seed=5)
    np.testing.assert_array_equal(
        attention(q, k, v, causal=True, window=20),
        dense_attention(q, k, v, True, window=20))
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, causal=False, window=20)


def test_the_backward_of_a_windowed_call_is_refused():
    q, k, v = _qkv(1, 300, 300, 2, 2, 16, seed=6)

    def loss(window):
        return lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=True, window=window).sum()
    with pytest.raises(NotImplementedError, match="window=64"):
        jax.grad(loss(64))(q, k, v)
    # ... and only of a windowed one
    assert jax.grad(loss(0))(q, k, v).shape == q.shape


@pytest.mark.parametrize("window,causal", [(-1, True), (8, False)])
def test_a_window_that_is_none_is_refused(window, causal):
    q, k, v = _qkv(1, 64, 64, 2, 2, 16)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=causal, interpret=True,
                           window=window)


# (lq, lk, d, q_offset, k_offset, window, block caps or None for the
#  interpreter's) -> (tiles run, masked, in the square, fetch blocks run,
#  grid steps) or None: held to the mask alone
_PLANS = {
    "the_cell_s_sliding_layer": ((16384, 16384, 128, 0, 0, 1024, 1024),
                                 (620, 248, 8192, 31, 32)),
    "the_cell_s_full_layer": ((16384, 16384, 128, 0, 0, 0, 1024),
                              (4160, 128, 8192, 136, 256)),
    "one_key_short": ((4096, 4096, 128, 0, 0, 1023, 1024), None),
    "one_key_over_a_block": ((4096, 4096, 128, 0, 0, 1025, 1024), None),
    "two_blocks_wide": ((8192, 8192, 64, 0, 0, 2048, 1024), None),
    "inside_a_tile": ((2048, 2048, 64, 0, 0, 100, 1024), None),
    "ragged_keys": ((2100, 2100, 64, 0, 0, 700, 1024), None),
    "offsets_inside_a_tile": ((1024, 3072, 64, 1500, 100, 900, 1024), None),
    "keys_ahead_of_every_query": ((512, 512, 64, 0, 2048, 256, 1024),
                                  (0, 0, 8, 0, 1)),
    "interpreter_blocks": ((640, 640, 32, 0, 0, 100, None), None),
    "interpreter_wide": ((900, 900, 16, 0, 0, 300, None), None),
}


@pytest.mark.parametrize("case", sorted(_PLANS))
def test_tile_plan_with_a_window(monkeypatch, case):
    """The plan's run tiles cover exactly the pairs the window, the
    diagonal and the padding leave; its band holds every block with such
    a pair; and the stretch a tile masks is the dense mask's."""
    (lq, lk, d, q_offset, k_offset, window, cap), want = _PLANS[case]
    if cap:
        monkeypatch.setattr(fa, "_block_caps", lambda d: (cap, cap))
    plan = fa.tile_plan(lq, lk, d, True, q_offset, k_offset, window)
    (nq, nk), (ni, nj) = plan.grid, plan.tiles
    rows = np.arange(nq * plan.bq)[:, None] + q_offset
    cols = np.arange(nk * plan.bk)[None, :] + k_offset
    allowed = (cols - k_offset < lk) & (rows >= cols)
    if window:
        allowed = allowed & (rows - cols < window)
    seen = {"bare": 0, "masked": 0, "skipped": 0}
    blocks_run = 0
    for qi in range(nq):
        lo, hi = plan.band(qi) if window else (0, nk - 1)
        assert hi - lo + 1 <= plan.band_blocks
        for ki in range(nk):
            block = allowed[qi * plan.bq:(qi + 1) * plan.bq,
                            ki * plan.bk:(ki + 1) * plan.bk]
            if not lo <= ki <= hi:
                # outside the band: never fetched, and nothing to see
                assert not block.any(), (qi, ki)
                seen["skipped"] += ni * nj
                continue
            kind = plan.block_kind(qi, ki)
            assert kind in plan.kinds()
            assert plan.runs(kind) == block.any(), (qi, ki)
            blocks_run += block.any()
            for i in range(ni):
                bare, run = plan.key_span(kind, i)
                start, clear = plan.key_start(kind, i)
                for j in range(nj):
                    tile = block[i * plan.tq:(i + 1) * plan.tq,
                                 j * plan.tk:(j + 1) * plan.tk]
                    got = plan.tile_kind(kind, i, j)
                    assert got == ("bare" if tile.all() else "masked"
                                   if tile.any() else "skipped"), \
                        (qi, ki, i, j)
                    seen[got] += 1
                if run <= start:
                    continue
                # the forward masks one stretch of the tile's walk, as
                # the kernel computes it, and the rest is all allowed
                lo_t = start if clear > start else max(bare, start)
                hi_t = run if run > bare else min(clear, run)
                r0 = i * plan.tq
                walk = block[r0:r0 + plan.tq]
                assert walk[:, start * plan.tk:lo_t * plan.tk].all()
                assert walk[:, hi_t * plan.tk:run * plan.tk].all()
                assert not walk[:, :start * plan.tk].any()
                assert not walk[:, run * plan.tk:].any()
                if hi_t > lo_t:
                    np.testing.assert_array_equal(
                        np.asarray(fa._valid_mask(
                            plan, kind, r0, plan.tq, lo_t * plan.tk,
                            (hi_t - lo_t) * plan.tk)),
                        walk[:, lo_t * plan.tk:hi_t * plan.tk])
    counts = plan.counts()
    assert counts == {"tiles_square": sum(seen.values()),
                      "tiles_run": seen["bare"] + seen["masked"],
                      "tiles_masked": seen["masked"],
                      "blocks_run": blocks_run,
                      "blocks_grid": nq * plan.band_blocks}
    if want:
        assert tuple(counts[k] for k in (
            "tiles_run", "tiles_masked", "tiles_square", "blocks_run",
            "blocks_grid")) == want


def test_the_band_as_a_program_reads_it():
    """``band`` of a traced block index (what the kernel and the K/V
    index map compute) is ``band`` of the int."""
    plan = fa.TilePlan(5000, 5000, 256, 256, 128, 128, True, 300, 40, 700)
    for qi in range(plan.grid[0]):
        lo, hi = jax.jit(plan.band)(jnp.int32(qi))
        assert (int(lo), int(hi)) == plan.band(qi)
    spec = fa._kv_block(256, 64, 4, 2, plan)
    lo, hi = plan.band(7)
    assert spec.index_map(9, 7, 0) == (2, lo, 0)
    # a step past the band's end names its last block again
    assert [int(spec.index_map(9, 7, j)[1]) for j in range(6)] == [
        min(lo + j, hi) for j in range(6)]


# sha256 of str(jax.make_jaxpr(...)) of a call without a window, taken at
# commit 58ad1bc (the parent of the PR that brought the window) with this
# test's own code: the whole call, forward and gradient, at the GPT-2
# train cell's shape and at the LFM2 cell's, traces to the same program
_PARENT = {
    ("gpt2_train", "fwd"):
        "198ec763787fc35dfd04826de6c968156dc2c68dc264ad489307c9f86ae20a00",
    ("gpt2_train", "grad"):
        "4010c41bf155d29150cd439dd69a17ebc74e9c1990262f7962b53ca34f2b418e",
    ("lfm2", "fwd"):
        "cc28e84b7964c27e79bec4d098354e40feadac94d1fca0d8ee3667a9b2314556",
    ("lfm2", "grad"):
        "9dffa6ad78cf1e2004529e4d7a7129f4a8c8fa59a2d14cb27e602d314d1175cd",
}
_SHAPES = {"gpt2_train": (8, 1024, 16, 16, 64), "lfm2": (4, 8192, 32, 8, 64)}


@pytest.mark.parametrize("shape,what", sorted(_PARENT))
def test_a_call_without_a_window_traces_to_the_parent_s_jaxpr(shape, what):
    b, length, h, hk, d = _SHAPES[shape]
    q = jax.ShapeDtypeStruct((b, length, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, length, hk, d), jnp.bfloat16)

    def call(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)
    if what == "grad":
        fn = jax.grad(lambda q, k, v: call(q, k, v).astype(
            jnp.float32).sum(), (0, 1, 2))
    else:
        fn = call
    text = str(jax.make_jaxpr(fn)(q, k, k))
    assert "flash_attention.py" not in text     # no line numbers in it
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT[(shape, what)]
    # ... and a window changes it (the grid's key axis is the band)
    windowed = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=256))(q, k, k))
    assert windowed != str(jax.make_jaxpr(call)(q, k, k))
