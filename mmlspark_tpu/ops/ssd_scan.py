"""The selective scan of a Mamba-2 layer by the chunked SSD algorithm
(Dao & Gu 2024, "Transformers are SSMs", section 6): on a TPU one Pallas
kernel at the shapes it takes (``kernel_runs``), elsewhere in
``jax.numpy``.

A head h of P channels carries a state S (P, N) along the sequence:

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T,   S_{-1} = 0
    y_t = S_t C_t (+ D_h x_t)

with B_t and C_t (N,) shared by the heads of a group. Stepping through t
is l dependent steps; the chunked algorithm cuts the row into chunks of
``chunk`` positions and gets the same y from dense products:

1. within a chunk, y_t = sum_{s <= t} exp(A_t - A_s) (C_t . B_s) dt_s x_s,
   A the running sum of the log-decays dt a (a masked (T, T) product a
   head, the decays from the segment sums of the log-decays);
2. the state each chunk ends in, from zero;
3. the state that enters each chunk, by the recurrence over the chunks'
   own (a (c + 1, c + 1) product of their total decays);
4. what that entering state gives each position, exp(A_t) S_in C_t;
   and then the D skip.

The algorithm is exact: the chunk size moves rounding alone. A row that
is no multiple of the chunk is padded with positions of dt = 0, which
neither decay the state nor add to it, so the final state is the last
real position's. Matrix products take ``x.dtype`` operands and
accumulate in float32; the decays, the states and the sums are float32,
in both forms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def segment_sums(a):
    """a (..., T) -> (..., T, T): sum_{k = s + 1 .. t} a_k at [t, s] for
    t >= s (0 on the diagonal), -inf above it, so that exp gives the
    causal decay from s to t."""
    total = jnp.cumsum(a, axis=-1)
    n = a.shape[-1]
    below = jnp.tril(jnp.ones((n, n), bool))
    return jnp.where(below, total[..., :, None] - total[..., None, :],
                     -jnp.inf)


def ssd_scan(x, dt, a, b, c, chunk: int, d=None):
    """x (batch, l, H, P); dt (batch, l, H) float32, the step after its
    softplus; a (H,) float32, negative; b, c (batch, l, G, N), head h of
    group h // (H / G); d (H,) or None. Returns (y (batch, l, H, P)
    float32, the final states (batch, H, P, N) float32). The kernel
    where it runs (``kernel_runs``), else the ``jax.numpy`` steps.
    XLA cannot partition the kernel: inside a jit over several devices
    the caller places it per shard (``Mamba2Mixer`` by
    ``ring_attention.flash_per_shard``)."""
    heads, p = x.shape[2:]
    groups, n = b.shape[2:]
    if heads % groups:
        raise ValueError(f"{heads} heads over {groups} groups")
    if kernel_runs(heads, p, n, groups, chunk):
        return _ssd_kernel(x, dt, a, b, c, d, chunk=chunk,
                           block=head_block(heads, p, groups))
    return _chunked(x, dt, a, b, c, chunk, d)


def _padded(chunk, *rows):
    """(batch, l, ...) arrays padded along l with zeros to whole chunks
    (a padded position's dt is 0)."""
    pad = -rows[0].shape[1] % chunk
    return tuple(jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                 for v in rows) if pad else rows


def _chunked(x, dt, a, b, c, chunk, d):
    """Steps 1-4 and the D skip in ``jax.numpy``."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, op = heads // groups, x.dtype
    x, dt, b, c = _padded(chunk, x, dt, b, c)
    nc = x.shape[1] // chunk
    xs = x.reshape(batch, nc, chunk, groups, r, p)
    steps = dt.astype(_F32).reshape(batch, nc, chunk, groups, r)
    bs = b.reshape(batch, nc, chunk, groups, n)
    cs = c.reshape(batch, nc, chunk, groups, n)
    # the log-decays, positions last: (batch, nc, G, r, T)
    logs = jnp.moveaxis(steps * a.astype(_F32).reshape(groups, r), 2, -1)
    cum = jnp.cumsum(logs, axis=-1)
    inputs = xs.astype(_F32) * steps[..., None]         # dt_t x_t
    # 1. within a chunk
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cs, bs,
                        preferred_element_type=_F32)
    mixed = scores[:, :, :, None] * jnp.exp(segment_sums(logs))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mixed.astype(op),
                   inputs.astype(op), preferred_element_type=_F32)
    # 2. the state each chunk ends in, from zero
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)
    ends = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bs,
                      (inputs * to_end[..., None]).astype(op),
                      preferred_element_type=_F32)
    # 3. the state that enters each chunk, and the one the row ends in
    totals = jnp.pad(cum[..., -1], [(0, 0), (1, 0), (0, 0), (0, 0)])
    carry = jnp.exp(segment_sums(jnp.moveaxis(totals, 1, -1)))
    ends = jnp.pad(ends, [(0, 0), (1, 0)] + [(0, 0)] * 4)
    entering = jnp.einsum("bgrzc,bcgrpn->bzgrpn", carry, ends,
                          precision=_HI)
    final = entering[:, -1].reshape(batch, heads, p, n)
    # 4. what the entering state gives each position
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cs,
                       entering[:, :-1].astype(op),
                       preferred_element_type=_F32) * from_start[..., None]
    y = y.reshape(batch, nc * chunk, heads, p)[:, :length]
    if d is not None:
        y = y + d.astype(_F32)[:, None] * x[:, :length].astype(_F32)
    return y, final


def recurrence(x, dt, a, b, c, d=None):
    """The same by stepping through the positions (``lax.scan`` over
    t), float32 at highest precision: what ``ssd_scan`` is held to."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    x, dt = x.astype(_F32), dt.astype(_F32)
    b = jnp.repeat(b.astype(_F32), heads // groups, axis=2)
    c = jnp.repeat(c.astype(_F32), heads // groups, axis=2)

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=_HI)
    s0 = jnp.zeros((batch, heads, p, n), _F32)
    final, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + d.astype(_F32)[:, None] * x
    return y, final


# ------------------------------------------------------------ the kernel

# lanes of a vector register: a tile of x, y and the states is 128 lanes
# wide, 128 / P heads side by side, and the (T, T) decays are built a
# 128 x 128 block at a time
_LANES = 128
_LOG2_E = 1.4426950408889634


def head_block(heads: int, p: int, groups: int) -> int:
    """Heads a grid step of the kernel carries: the most, up to 128, that
    divide a group's heads and fill whole tiles of 128 lanes (0 where
    none do)."""
    r = heads // groups
    fits = [h for h in range(1, min(r, _LANES) + 1)
            if r % h == 0 and h * p % _LANES == 0]
    return max(fits, default=0)


def kernel_runs(heads: int, p: int, n: int, groups: int,
                chunk: int) -> bool:
    """Whether ``ssd_scan`` runs the kernel: on a TPU, where the chunk
    and the state are multiples of 128, a head's P channels divide a
    tile of 128 lanes, and a block of whole tiles lies inside one group
    (``head_block``). Elsewhere the ``jax.numpy`` steps run."""
    return (jax.default_backend() == "tpu" and chunk % _LANES == 0
            and n % _LANES == 0 and _LANES % p == 0
            and head_block(heads, p, groups) > 0)


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def _ssd_kernel(x, dt, a, b, c, d, *, chunk, block, interpret=False):
    """The chunked scan as one Pallas kernel; ``ssd_scan``'s contract,
    ``block`` heads a grid step (``head_block``). The grid runs over
    (row, head block, chunk), the chunks innermost and in order: the
    block's states, (N, block P) float32 with head h's S^T in lanes
    [h P, h P + P), stay in VMEM from one chunk to the next (zero at
    chunk 0, then S <- exp(A_T) S + the chunk's own end state, element
    by element). A chunk's C B^T (T, T) is formed once for the block's
    group and kept, masked to t >= s; then a rolled loop over the
    block's tiles of 128 lanes forms, for each head of a tile, its
    decays exp(A_t - A_s) in the 128 x 128 blocks of the lower triangle
    (a block above the diagonal is never made), their product with
    C B^T cast to ``x.dtype`` against the tile's dt x (the head's own
    lanes of the result kept), and for the whole tile exp(A_t) C S_in,
    the end state B^T (exp(A_T - A_s) dt_s x_s) and the D skip; y is
    written once. The running sums A of the log-decays are formed
    outside, a chunk at a time and times log2 e (the kernel takes
    powers of two), and come in twice: as rows (a head's A_s along the
    lanes) and as columns (heads along the lanes, where a lane gather
    gives each lane its head's A_t, and dt likewise). Nothing (T, T)
    leaves VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    op, r = x.dtype, heads // groups
    blocks, per_tile, tiles = heads // block, _LANES // p, block * p // _LANES
    x, dt, b, c = _padded(chunk, x, dt, b, c)
    lp = x.shape[1]
    nc = lp // chunk
    dt = dt.astype(_F32)
    # the running sums in powers of two: exp(A_t - A_s) = 2^(A'_t - A'_s)
    cum = jnp.cumsum((dt * a.astype(_F32)).reshape(batch, nc, chunk, heads),
                     axis=2).reshape(batch, lp, heads) * _LOG2_E
    # a head's running sums along the lanes: (batch, blocks, rows, lp),
    # the block's heads padded to whole sublane tiles of 8
    rows = -(-block // 8) * 8
    cum_rows = jnp.pad(jnp.swapaxes(cum, 1, 2).reshape(
        batch, blocks, block, lp), [(0, 0), (0, 0), (0, rows - block),
                                    (0, 0)])

    def columns(v):     # (batch, lp, H) -> (batch, lp, blocks x 128)
        v = v.reshape(batch, lp, blocks, block)
        return jnp.pad(v, [(0, 0)] * 3 + [(0, _LANES - block)]).reshape(
            batch, lp, blocks * _LANES)
    b_t = jnp.swapaxes(b.reshape(batch, lp, groups * n), 1, 2)
    operands = [x.reshape(batch, lp, heads * p), b_t,
                c.reshape(batch, lp, groups * n), cum_rows, columns(cum),
                columns(dt)]

    def group(j):
        return j * block // r
    specs = [
        pl.BlockSpec((None, chunk, block * p), lambda i, j, k: (i, k, j)),
        pl.BlockSpec((None, n, chunk), lambda i, j, k: (i, group(j), k)),
        pl.BlockSpec((None, chunk, n), lambda i, j, k: (i, k, group(j))),
        pl.BlockSpec((None, None, rows, chunk),
                     lambda i, j, k: (i, j, 0, k)),
        pl.BlockSpec((None, chunk, _LANES), lambda i, j, k: (i, k, j)),
        pl.BlockSpec((None, chunk, _LANES), lambda i, j, k: (i, k, j))]
    if d is not None:       # D a lane: (1, H P)
        operands.append(jnp.repeat(d.astype(_F32), p)[None])
        specs.append(pl.BlockSpec((1, block * p), lambda i, j, k: (0, j)))
    sub = chunk // _LANES

    def kernel(x_ref, bt_ref, c_ref, rows_ref, cum_ref, dt_ref, *rest):
        d_ref = rest[0] if d is not None else None
        y_ref, final_ref, state_ref, cb_ref = rest[-4:]
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            state_ref[...] = jnp.zeros(state_ref.shape, _F32)

        scores = jnp.dot(c_ref[...], bt_ref[...],
                         preferred_element_type=_F32)
        t_ = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        s_ = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        cb_ref[...] = jnp.where(t_ >= s_, scores, 0.0)
        # the head of a lane of a tile, among the tile's heads: over the
        # chunk's rows and over a block of 128 of them
        head_of_lane = lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1) // p
        block_head = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1) // p

        def tile(j, carry):
            lanes = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
            heads_here = j * per_tile + head_of_lane
            # the tile's heads' A'_t and dt_t, each lane its head's
            at = jnp.take_along_axis(cum_ref[...], heads_here, axis=1)
            xs = x_ref[:, lanes].astype(_F32)
            inputs = xs * jnp.take_along_axis(dt_ref[...], heads_here,
                                              axis=1)           # dt_t x_t
            s_in = state_ref[:, lanes]
            entering = s_in.astype(op)
            total = at[chunk - 1:]
            ends = jnp.dot(bt_ref[...],
                           (inputs * jnp.exp2(total - at)).astype(op),
                           preferred_element_type=_F32)
            state_ref[:, lanes] = jnp.exp2(total) * s_in + ends
            mixable = inputs.astype(op)
            for ti in range(sub):
                rows_t = slice(ti * _LANES, (ti + 1) * _LANES)
                y = jnp.exp2(at[rows_t]) * jnp.dot(
                    c_ref[rows_t, :], entering, preferred_element_type=_F32)
                # each head's decays against the whole tile's dt_s x_s;
                # the lanes of its own channels are kept
                for h in range(per_tile):
                    own = 0.0
                    head = j * per_tile + h
                    a_t = jnp.take_along_axis(
                        cum_ref[rows_t, :],
                        jnp.full((_LANES, _LANES), head, jnp.int32), axis=1)
                    # A'_s on all 8 sublanes: the head's row of the
                    # aligned 8 it lies in, gathered along the sublanes
                    eight = rows_ref[pl.ds(pl.multiple_of(head // 8 * 8, 8),
                                           8), :]
                    a_s = jnp.take_along_axis(
                        eight, jnp.full((8, chunk), head % 8, jnp.int32),
                        axis=0)
                    for si in range(ti + 1):
                        cols_s = slice(si * _LANES, (si + 1) * _LANES)
                        gap = a_t - jnp.tile(a_s[:, cols_s], (_LANES // 8, 1))
                        if si == ti:    # above the diagonal: masked out
                            gap = jnp.minimum(gap, 0.0)
                        mixed = jnp.exp2(gap) * cb_ref[rows_t, cols_s]
                        own = own + jnp.dot(mixed.astype(op),
                                            mixable[cols_s],
                                            preferred_element_type=_F32)
                    y = y + jnp.where(block_head == h, own, 0.0)
                if d_ref is not None:
                    y = y + d_ref[:, lanes] * xs[rows_t]
                y_ref[rows_t, lanes] = y
            return carry
        lax.fori_loop(0, tiles, tile, 0)

        @pl.when(k == nc - 1)
        def _():
            final_ref[...] = state_ref[...]

    width = block * p
    y, final = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((batch, lp, heads * p), _F32),
                   jax.ShapeDtypeStruct((batch, n, heads * p), _F32)),
        grid=(batch, blocks, nc),
        in_specs=specs,
        out_specs=(
            pl.BlockSpec((None, chunk, width), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((None, n, width), lambda i, j, k: (i, 0, j))),
        scratch_shapes=[pltpu.VMEM((n, width), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(chunk, width, n, rows, op)),
        interpret=interpret,
        name="ssd_scan",
    )(*operands)
    y = y.reshape(batch, lp, heads, p)[:, :length]
    final = jnp.moveaxis(final.reshape(batch, n, heads, p), 1, -1)
    return y, final


def _vmem_bytes(chunk, width, n, rows, op) -> int:
    """The kernel's VMEM: every block twice (the pipeline's two buffers),
    the states and C B^T, and as much again for what the compiler keeps,
    at least the default 32 MiB."""
    item = jnp.dtype(op).itemsize
    blocks = (chunk * width * (item + 4) + n * width * 4
              + 2 * chunk * n * item + rows * chunk * 4
              + 2 * chunk * _LANES * 4 + width * 4)
    need = 2 * (2 * blocks + n * width * 4 + chunk * chunk * 4)
    return min(max(need, 32 * 2 ** 20), 100 * 2 ** 20)
