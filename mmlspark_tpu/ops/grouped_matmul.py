"""Grouped matrix product over the experts a chip holds.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

``lhs`` (m, k) holds the rows sorted by group, group g's rows next to
each other; ``rhs`` (groups, k, n) one matrix a group; ``group_sizes``
(groups,) int32 says how many rows each group has. Their sum may be
less than m: the rows past it belong to no group and come back as
zeros, unless the caller says that nothing reads them
(``rest_unread``): then they hold whatever the kernel left there, and
the pass over the whole output that would zero them is not made. On a
TPU this is the megablox Pallas kernel that ships with jax (its grid
runs over the tiles that hold rows, so the time follows the rows routed
here, not m); elsewhere ``lax.ragged_dot``. bfloat16 operands, float32
accumulation.

``grouped_swiglu`` is the first half of a gated feed-forward over the
same groups, in one kernel of this repo's own:

    into[lo + r] = silu(x[r] @ w_gate[g]) * (x[r] @ w_up[g])

with both products' float32 sums kept on the chip and one cast at the
store, its rows read by the kernel itself through their token ids
(below).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# m, k and n tile of the kernel: 512 rows amortise an expert's weight
# tile over the rows routed to it, 1024 x 1024 weight tiles stay under
# the default VMEM grant with double buffering. The k and n tiles follow
# the shape (``_tile``): the kernel wants a tile that divides the side
# (a ragged last k tile is masked element by element in every grid
# step), and 1536 is no multiple of 1024
TILING = (512, 1024, 1024)


def _tile(side: int, want: int) -> int:
    """The widest multiple of 128 up to ``want`` that divides ``side``
    (1024 for 2048 and 6144, 768 for 1536); ``side`` itself where it is
    no wider than ``want``, and ``want`` where nothing divides."""
    if side <= want:
        return side
    for t in range(want - want % 128, 0, -128):
        if side % t == 0:
            return t
    return want


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _moe_grouped_matmul(lhs, rhs, group_sizes, out_dtype):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = lhs.shape
    n = rhs.shape[2]
    tiling = (min(TILING[0], m), _tile(k, TILING[1]), _tile(n, TILING[2]))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=tiling)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=jnp.bfloat16, *,
                   rest_unread: bool = False):
    """``rest_unread`` (static) is the caller's word that no row past
    the groups' sum is ever read: a row of the product depends on that
    row of ``lhs`` alone and the kernel's store mask keeps a shared
    tile's foreign rows out, so whatever such a row holds (zeros off
    the chip) reaches nothing the caller does read."""
    group_sizes = group_sizes.astype(jnp.int32)
    m = lhs.shape[0]
    if jax.default_backend() == "tpu" and m % min(TILING[0], m) == 0:
        out = _moe_grouped_matmul(lhs, rhs, group_sizes, out_dtype)
    else:
        out = jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(out_dtype)
    if rest_unread:
        return out
    # rows of no group: the kernel leaves them unwritten
    in_group = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(in_group[:, None], out, jnp.zeros((), out_dtype))


# ``grouped_swiglu``'s tiles. The k tile is the whole of k: an expert's
# two weight blocks then keep their block index across that expert's
# row tiles and are fetched once a group and n tile, where a k tile of
# half of k streams them again for every row tile. The n tile is the
# widest ``_tile`` of n whose two double-buffered weight blocks stay
# under SWIGLU_WEIGHT_BYTES (all of n at the served widths: 25 MB for
# 2048 x 1536 bfloat16, 17 MB for 2304 x 896), so ``x`` is read once.
# With the weights resident a row tile need not amortise them: 256 rows
# halve the tiles two experts share and both visit (16 of 144 visits a
# pass of 32,768 rows over 16 experts, where 512 rows make 16 of 80).
# On a v5e, a pass at LFM2's widths: 2.48 ms at 256 rows, 2.50 at 128,
# 2.69 at 512, 3.67 at 1024; at Mellum2's: 1.56, 1.59, 1.62 (PERF.md
# section 6, PR 39)
SWIGLU_ROWS = 256
SWIGLU_WEIGHT_BYTES = 48 * 2 ** 20


def _swiglu_tiles(m: int, k: int, n: int, itemsize: int):
    """(row tile, n tile) for (m, k) rows against (k, n) weights of
    ``itemsize`` bytes an element."""
    tn = n
    while 4 * k * tn * itemsize > SWIGLU_WEIGHT_BYTES and tn > 128:
        narrower = _tile(n, tn - 128)
        if n % narrower:        # nothing narrower divides n
            break
        tn = narrower
    return min(SWIGLU_ROWS, m), tn


# rows whose copies a visit starts from one trip of its rolled loop: the
# kernel's body has the same size at any row tile
_COPY_UNROLL = 8
_BF16 = jnp.dtype(jnp.bfloat16)


def _reads_through_ids(m: int, x) -> bool:
    """Whether ``grouped_swiglu`` runs its kernel on a pass of ``m``
    rows of ``x``: on a TPU, where the row tile divides m, for float32
    or bfloat16 rows (what a table of 32-bit words holds exactly)."""
    return (jax.default_backend() == "tpu"
            and m % min(SWIGLU_ROWS, m) == 0
            and x.dtype in (jnp.float32, _BF16))


def row_table(x, m: int):
    """``x`` (tokens, k) in the form ``grouped_swiglu`` reads a pass of
    ``m`` rows from through their ids (``x`` itself where it reads none,
    ``_reads_through_ids``). A copy on the chip moves whole tiles, and a
    tile of a 2-D array spans 8 rows (16 of a 16-bit dtype, two to a
    word), so the table holds a row as 32-bit words under a unit axis of
    its own, (tokens, 1, width): its tiles are the row's alone. Made once
    where several passes read rows of one ``x``."""
    return _table(x) if _reads_through_ids(m, x) else x


def _table(x):
    """float32 rows as they are; a bfloat16 row of k as h = ceil(k / 2)
    words, word j holding element j in its low half and element h + j
    (where there is one) in its high half: the row's two halves, which
    the kernel puts side by side again (``_rows``), exact."""
    tokens, k = x.shape
    if x.dtype != _BF16:
        return x.reshape(tokens, 1, k)
    half = -(-k // 2)
    bits = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    high = jnp.pad(bits[:, half:], ((0, 0), (0, 2 * half - k)))
    return (bits[:, :half] | (high << 16)).reshape(tokens, 1, half)


def _rows(words, dtype, k: int):
    """The (rows, k) rows in ``dtype`` that (rows, width) ``_table``
    words hold: a bfloat16 value is the high half of the float32 of the
    same value. At the served widths (k a multiple of 256) the two
    halves are whole 128-lane tiles."""
    if dtype != _BF16:
        return words
    low = lax.bitcast_convert_type(words << 16, jnp.float32)
    high = lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                    jnp.float32)
    return jnp.concatenate([low, high[:, :k - words.shape[1]]],
                           axis=1).astype(dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_swiglu(table, tok, w_gate, w_up, group_sizes, into, lo, *,
                    tiles, interpret=False):
    """The kernel. The grid runs over the n tiles and, inside each,
    over the row tiles that hold rows (megablox's metadata: a tile two
    groups share is visited once for each, one after the other, and a
    store mask keeps each visit to its own rows; tiles past the groups'
    sum are not visited). Row r of the pass is ``table[tok[r]]``
    (``_table`` of float32 rows, or of bfloat16 rows as 32-bit words):
    the table stays in HBM and ``tok`` in SMEM, and a visit's rows come
    into one of two VMEM slots by one copy a row, started a visit
    ahead, and reach the products in their own dtype: visit v waits for
    its rows (started at v - 1, or at the n tile's first visit for
    itself), starts visit v + 1's into the other slot and then
    multiplies, so the copies run under the products. A visit of the
    tile the last one visited (a tile two groups share) finds its rows
    in the slot and starts nothing. ``into`` is aliased to the output
    and never read: the output's row-block index is offset by ``lo //
    tm``, so the rows land in slice ``lo`` of the buffer and every
    other block of it is left as it was."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    m, tokens, width = tok.shape[0], table.shape[0], table.shape[2]
    k, n = w_gate.shape[1], w_gate.shape[2]
    dtype = _BF16 if table.dtype == jnp.uint32 else table.dtype
    tm, tn = tiles
    unroll = math.gcd(tm, _COPY_UNROLL)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=w_gate.shape[0], visit_empty_groups=False)
    first_block = (jnp.asarray(lo, jnp.int32) // tm).reshape(1)

    def kernel(offsets, group_ids, tile_ids, first_block, tok, table_hbm,
               gate_ref, up_ref, into_ref, out_ref, slot_0, slot_1, copied,
               slot_of):
        del first_block, into_ref
        visit, last = pl.program_id(1), pl.num_programs(1) - 1
        tile = tile_ids[visit]
        # two refs and a branch on which, not one (2, tm, 1, width) ref:
        # rows read from a slice of a 4-D ref cost the products twice
        # the vector work (the static schedule, a v5e)
        slots = (slot_0, slot_1)

        def fetch(tile, slot):
            def rows(trip, carry):
                for j in range(unroll):
                    r = trip * unroll + j
                    # an id names a row of the table by construction;
                    # held inside it here, where the compiler's bounds
                    # check of each copy would take twice the copy's
                    # own instructions
                    row = jnp.clip(tok[tile * tm + r], 0, tokens - 1)
                    pltpu.make_async_copy(
                        table_hbm.at[pl.ds(row, 1)],
                        slots[slot].at[pl.ds(r, 1)],
                        copied.at[slot]).start()
                return carry
            lax.fori_loop(0, tm // unroll, rows, 0)

        def multiply(slot_ref):
            group = group_ids[visit]
            row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
            mine = (row >= offsets[group]) & (row < offsets[group + 1])
            rows = _rows(slot_ref[:, 0, :], dtype, k)
            gate = jnp.dot(rows, gate_ref[...],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(rows, up_ref[...],
                         preferred_element_type=jnp.float32)
            h = (jax.nn.silu(gate) * up).astype(out_ref.dtype)
            # a shared tile's other rows: the neighbour's visit wrote
            # them, or will
            out_ref[...] = jnp.where(mine, h, out_ref[...])

        @pl.when(visit == 0)
        def _():
            slot_of[0] = 0
            fetch(tile, 0)

        slot = slot_of[0]

        # the slot's semaphore counts bytes: one wait for all tm rows,
        # described by a copy of a whole slot
        @pl.when((visit == 0)
                 | (tile != tile_ids[jnp.maximum(visit - 1, 0)]))
        def _():
            pltpu.make_async_copy(slot_0, slot_1, copied.at[slot]).wait()

        following = tile_ids[jnp.minimum(visit + 1, last)]
        for s in (0, 1):
            @pl.when((slot == s) & (visit < last) & (following != tile))
            def _():
                fetch(following, 1 - s)
                slot_of[0] = 1 - s

            pl.when(slot == s)(functools.partial(multiply, slots[s]))

    def weight_block(n_i, visit, offsets, group_ids, tile_ids, first_block,
                     tok):
        return group_ids[visit], 0, n_i

    def out_block(n_i, visit, offsets, group_ids, tile_ids, first_block,
                  tok):
        return first_block[0] + tile_ids[visit], n_i

    weights = pl.BlockSpec((None, k, tn), weight_block)
    row_bytes, out_item = width * 4, into.dtype.itemsize
    # the two slots of rows, the weight blocks twice (the pipeline's two
    # buffers), the float32 sums and their product, and as much again
    # for what the compiler keeps
    vmem = 2 * (2 * (tm * row_bytes + 2 * k * tn * w_gate.dtype.itemsize
                     + tm * tn * out_item) + 4 * tm * tn * 4)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), weights, weights,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), out_block),
            scratch_shapes=[pltpu.VMEM((tm, 1, width), table.dtype),
                            pltpu.VMEM((tm, 1, width), table.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)],
            grid=(n // tn, visits)),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(max(vmem, 32 * 2 ** 20), 112 * 2 ** 20),
            disable_bounds_checks=True),
        cost_estimate=pl.CostEstimate(
            flops=4 * m * k * n, transcendentals=m * n,
            bytes_accessed=(n // tn) * m * row_bytes + m * n * out_item
            + 2 * w_gate.size * w_gate.dtype.itemsize),
        interpret=interpret,
        name="grouped_swiglu",
    )(offsets, group_ids, tile_ids, first_block, tok, table, w_gate, w_up,
      into)


def grouped_swiglu(table, w_gate, w_up, group_sizes, into, lo, *, tok):
    """``into`` with rows [lo, lo + m) set to silu(x @ w_gate[g]) *
    (x @ w_up[g]) for the rows of group g, where row r of ``x`` is row
    ``tok[r]`` of a (tokens, k) array and ``table`` is that array's
    ``row_table(array, m)``: ``tok`` (m,) int32 with the rows sorted by
    group, ``w_gate`` and ``w_up`` (groups, k, n), ``into`` (a multiple
    of m, n) in the dtype the rows are kept in (the array's where
    ``routed_experts`` calls), ``lo`` a traced multiple of m. On the chip
    the kernel reads each row through its id, and no (m, k) array of
    the rows exists outside it. Operands as they come, both sums, silu
    and the product in float32, one cast at the store: what two
    ``grouped_matmul`` to float32 and the element-wise operations after
    them compute, without the float32 rows ever leaving the chip's
    VMEM. Rows past the groups' sum belong to no group: their rows of
    ``into`` hold whatever they held or the kernel left there, and the
    caller reads none of them (``rest_unread``). Where the kernel does
    not run (``_reads_through_ids``): the gather ``x[tok]``, two
    ``lax.ragged_dot`` and a ``dynamic_update_slice``."""
    group_sizes = group_sizes.astype(jnp.int32)
    m, k = tok.shape[0], w_gate.shape[1]
    if table.ndim == 3:
        tiles = _swiglu_tiles(m, k, w_gate.shape[2], w_gate.dtype.itemsize)
        return _grouped_swiglu(table, tok.astype(jnp.int32), w_gate, w_up,
                               group_sizes, into, lo, tiles=tiles)
    x = table[tok]
    gate, up = (lax.ragged_dot(x, w, group_sizes,
                               preferred_element_type=jnp.float32)
                for w in (w_gate, w_up))
    h = (jax.nn.silu(gate) * up).astype(into.dtype)
    return lax.dynamic_update_slice_in_dim(into, h, lo, 0)
