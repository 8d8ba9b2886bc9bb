"""Pallas TPU kernel for GBDT histogram building.

The histogram is the GBDT hot op (the reference spends its training time
inside LightGBM's native C++ histogram loop, ref: TrainUtils.scala:82-89).
On TPU the scatter-free formulation is histogram-by-matmul: for a chunk
of rows, build the bin one-hot in VMEM and contract it against the
per-row stats with one MXU matmul, accumulating all (feature, bin, leaf)
cells of the chunk at once. Scatter/segment_sum is hundreds of times
slower on TPU (serialized scatter units), and the XLA onehot path
round-trips the one-hot through HBM; this kernel keeps it in VMEM.

Two layout decisions carry the performance:
  - the matmul runs as (3L, C) @ (C, fc*B): the tiny stats dimension
    (3 for the single-leaf histograms the tree grower builds) lands in
    the MXU sublane axis where it pads 3->8, not the lane axis where it
    would pad 3->128 — a 16x difference in matmul work;
  - block shapes obey Mosaic's tiling rules ((8, 128)-divisible or
    full-dimension): bins arrive features-major (F, N) — the layout the
    whole GBDT engine stores — blocked (fc, C); num_bins is padded to a
    multiple of 32 so fc*B is always 128-divisible.

Row-chunk grid steps accumulate into the same output block, which is
safe because TPU grid iterations execute sequentially on a core.

Numerics match the scatter/segment-sum path to float32 tolerance; on
non-TPU backends the kernel runs in interpret mode (tests) and the
booster defaults to the scatter path instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

ROW_CHUNK = 512            # multiple of 128 (lane dim of the bins block)
ROW_CHUNK_SINGLE = 2048    # L==1 hot path: fewer grid steps (the per-
                           # step overhead dominates at C=512), bigger
                           # VMEM onehot block is affordable without the
                           # (3L, C) leaf-weighted lhs
VMEM_ONEHOT_BYTES = 8 << 20   # onehot block budget: c*fc*B*4 bytes


def _nibble_hl(b_pad: int):
    """Split B into hi*lo digits minimizing VPU work per row:
    hoh compares (h) + loh compares (l) + lhs multiplies (3h) = 4h + l,
    subject to h*l = B. Powers of two keep // and % cheap; l must be a
    multiple of 16 so the output block's lane dim (fc*l, fc=8) stays
    128-divisible — Mosaic rejects partial lane blocks. Returns None
    when no legal factorization exists (caller falls back to the
    direct one-hot kernel)."""
    best = None
    h = 2
    while h * 2 <= b_pad:
        l = b_pad // h
        if h * l == b_pad and l % 16 == 0:
            cost = 4 * h + l
            if best is None or cost < best[0]:
                best = (cost, h, l)
        h *= 2
    return (best[1], best[2]) if best else None


def _hist_kernel_nibble(bins_ref, stats_ref, out_ref, *, h: int, l: int,
                        acc_dtype=jnp.float32):
    """Single-leaf histogram via digit decomposition: bin = hi*l + lo,
    so 1[bin==b] = 1[hi==b_hi]*1[lo==b_lo] and the (3, B) histogram of
    one feature is the (3h, C) x (C, l) matmul of the stats-weighted
    hi-onehot against the lo-onehot — O(h + l) one-hot lanes per row
    instead of O(B), which is what bounds the kernel (the one-hot build
    is VPU-compare work; the matmuls are almost free on the MXU).

    Quantized stats keep the one-hots in the SAME narrow dtype and ask
    the MXU for an int32 accumulator via ``preferred_element_type`` —
    the i8->i32 lowering the quantized inference kernels use
    (core/quantize.py), giving exact integer histogram sums. Only int8
    compiles for TPU (the MXU has no int16 path; booster's
    _validate_hist_params refuses hist_bits=16 here).

    Output layout is (3h, fc*l) — feature j's (3h, l) block at columns
    [j*l, (j+1)*l) — because collapsing (h, l) into the lane axis is
    not a Mosaic-legal reshape; hist_pallas untangles it with one tiny
    XLA transpose on the final (3h, F*l) array."""
    r = pl.program_id(1)
    bins_blk = bins_ref[:]                         # (fc, C) int32
    stats_blk = stats_ref[:]                       # (3, C) f32|int
    fc, c = bins_blk.shape

    hi = bins_blk // l                             # (fc, C)
    lo = bins_blk - hi * l
    hi_ids = lax.broadcasted_iota(jnp.int32, (h, c), 0)
    lo_ids = lax.broadcasted_iota(jnp.int32, (l, c), 0)

    oh_dtype = stats_blk.dtype
    # Mosaic has an int8 matmul but no int8 vector multiply: quantized
    # stats weight the hi one-hot in int32 and narrow for the MXU
    mul_dtype = jnp.int32 if jnp.issubdtype(oh_dtype, jnp.integer) \
        else oh_dtype
    stats_mul = stats_blk.astype(mul_dtype)
    parts = []
    for j in range(fc):                            # static unroll
        hoh = (hi[j][None, :] == hi_ids).astype(mul_dtype)      # (h, C)
        loh = (lo[j][None, :] == lo_ids).astype(oh_dtype)       # (l, C)
        lhs = (stats_mul[:, None, :] * hoh[None, :, :]) \
            .reshape(3 * h, c).astype(oh_dtype)    # (3h, C)
        parts.append(lax.dot_general(
            lhs, loh, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype))     # (3h, l)
    contrib = jnp.concatenate(parts, axis=1)       # (3h, fc*l)

    @pl.when(r == 0)
    def _():
        out_ref[:] = contrib

    @pl.when(r > 0)
    def _():
        out_ref[:] = out_ref[:] + contrib


def _hist_kernel(bins_ref, stats_ref, leaf_ref, out_ref, *,
                 num_leaves: int, num_bins: int,
                 acc_dtype=jnp.float32):
    r = pl.program_id(1)

    bins_blk = bins_ref[:]                         # (fc, C) int32
    stats_blk = stats_ref[:]                       # (3, C) f32|int
    fc, c = bins_blk.shape
    oh_dtype = stats_blk.dtype

    # one-hot (fc*B, C): leading-dims collapse only (Mosaic cannot
    # reshape trailing dims into the lane axis). Quantized stats keep
    # the one-hot in the same narrow int dtype and accumulate int32
    # via preferred_element_type (i8->i32, cf. core/quantize.py).
    bin_ids = lax.broadcasted_iota(jnp.int32, (num_bins, c), 0)
    onehot = (bins_blk[:, None, :] == bin_ids[None, :, :]) \
        .astype(oh_dtype).reshape(fc * num_bins, c)

    if num_leaves == 1:
        lhs = stats_blk                            # (3, C)
    else:
        leaf_blk = leaf_ref[:]                     # (1, C) int32
        leaf_ids = lax.broadcasted_iota(jnp.int32, (num_leaves, c), 0)
        leaf_oh = (leaf_blk == leaf_ids).astype(oh_dtype)      # (L, C)
        lhs = (stats_blk[:, None, :] * leaf_oh[None, :, :]) \
            .reshape(3 * num_leaves, c)            # (3L, C)

    # NT matmul (contract the shared C axis): (3L, C) x (fc*B, C)^T.
    # The tiny 3L dim sits in the MXU sublane axis (pads 3->8), not the
    # lane axis (which would pad 3->128) — 16x less matmul work.
    contrib = lax.dot_general(
        lhs, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=acc_dtype)          # (3L, fc*B)

    @pl.when(r == 0)
    def _():
        out_ref[:] = contrib

    @pl.when(r > 0)
    def _():
        out_ref[:] = out_ref[:] + contrib


def _block_plan(f: int, n: int, num_bins: int, num_leaves: int):
    """The kernel's block geometry for TRUE input shape (f, n):
    returns (nibble, c, fc, b_pad, f_target, n_target). Both
    hist_pallas's internal padding and grow_tree's once-per-tree
    pre-padding (padded_bins_shape) derive from this single function,
    so they cannot drift.

    Routing: the single-leaf hot path (the tree grower only ever
    builds these) at B >= 128 takes the digit-decomposition kernel —
    VPU one-hot work per row drops from O(B) to O(4h + l), h*l = B
    (measured on v5e at HIGGS shape: 255-bin boost loop 16.4s -> 5.0s).
    At B < 128 the direct one-hot kernel is still faster (fewer,
    larger matmuls)."""
    b_pad = -(-num_bins // 32) * 32
    if num_leaves == 1 and b_pad >= 128 and _nibble_hl(b_pad):
        fc = min(8, f + ((-f) % 8))
        c = min(8192, max(512, n + ((-n) % 512)))
        return (True, c, fc, b_pad,
                f + ((-f) % fc), n + ((-n) % c))
    row_chunk = ROW_CHUNK_SINGLE if num_leaves == 1 else ROW_CHUNK
    row_cap = max(128, (VMEM_ONEHOT_BYTES // 4 // (8 * b_pad))
                  // 128 * 128)
    row_chunk = min(row_chunk, row_cap)
    if n >= row_chunk:
        c = row_chunk
    else:
        c = n + ((-n) % 8)          # single chunk, sublane-aligned
    elems = VMEM_ONEHOT_BYTES // 4 // c
    fc = max(8, (elems // b_pad) // 8 * 8)
    fc = min(fc, f + ((-f) % 8))
    if c * fc * b_pad * 4 > 2 * VMEM_ONEHOT_BYTES:
        # the fc/row floors could not respect the budget (huge num_bins)
        # — fail loudly rather than letting Mosaic's allocator throw a
        # cryptic compile error (booster routes such configs to onehot)
        raise ValueError(
            f"num_bins={num_bins} is beyond the Pallas histogram's VMEM "
            f"tiling range (block {c}x{fc}x{b_pad}); use "
            f"hist_method='onehot'")
    return (False, c, fc, b_pad,
            f + ((-f) % fc), n + ((-n) % c))


def padded_bins_shape(f: int, n: int, num_bins: int,
                      num_leaves: int = 1):
    """(f_target, n_target) the kernel will pad a TRUE (f, n) bins
    matrix to. Callers that invoke the histogram many times on the same
    bins (grow_tree: once per split) pre-pad ONCE to this shape and
    pass ``true_shape`` — profiling showed the per-call pad of the full
    (F, N) matrix was 17% of the boost loop."""
    _, _, _, _, f_t, n_t = _block_plan(f, n, num_bins, num_leaves)
    return f_t, n_t


@functools.partial(jax.jit,
                   static_argnames=("num_leaves", "num_bins",
                                    "interpret", "true_shape"))
def hist_pallas(bins: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                weight: jnp.ndarray, leaf_of_row: jnp.ndarray,
                num_leaves: int, num_bins: int,
                interpret: bool = False,
                true_shape=None,
                count_values=None) -> jnp.ndarray:
    """(3, L, F, B) histogram via the Pallas MXU kernel.

    ``bins`` is features-major (F, N) — consumed directly, no transpose.
    Same contract as histogram.build_histogram's other methods; rows
    with weight 0 (padding/bagging) contribute nothing.

    Float32 by default. Quantized mode (integer grad/hess from
    tree.py's hist_bits < 32 rounding): the stats block and the bin
    one-hot stay in the NARROW int dtype and the MXU accumulates int32
    via ``preferred_element_type`` — the same i8->i32 lowering the
    quantized inference kernels use — returning an exact (3, L, F, B)
    int32 histogram. ``count_values`` then carries the quantized
    per-row weight for the count channel (None keeps c = sum(weight)).

    ``true_shape=(f, n)`` marks ``bins`` as ALREADY padded to
    padded_bins_shape(f, n, ...): the per-call full-matrix pad is then
    a no-op (profiled at 17% of the boost loop when left inside the
    split loop); grad/hess/weight/leaf_of_row stay true-n sized and
    are padded here (cheap (N,) pads). The returned histogram is
    always sliced to the TRUE f."""
    f, n = true_shape if true_shape is not None else bins.shape

    nibble, c, fc, b_pad, f_tgt, n_tgt = _block_plan(
        f, n, num_bins, num_leaves)
    if bins.shape[0] > f_tgt or bins.shape[1] > n_tgt:
        raise ValueError(
            f"bins {bins.shape} exceed the kernel target "
            f"({f_tgt}, {n_tgt}) for true_shape ({f}, {n})")

    # ONE padding block for both kernel paths, keyed off the plan's
    # targets (pre-padded bins make these no-ops — see true_shape)
    pad_rows = n_tgt - bins.shape[1]
    pad_feats = f_tgt - bins.shape[0]
    stat_pad = n_tgt - n
    if pad_rows or pad_feats:
        bins = jnp.pad(bins, ((0, pad_feats), (0, pad_rows)))
    if stat_pad:
        grad = jnp.pad(grad, (0, stat_pad))
        hess = jnp.pad(hess, (0, stat_pad))
        weight = jnp.pad(weight, (0, stat_pad))   # 0-weight padding
        if count_values is not None:
            count_values = jnp.pad(count_values, (0, stat_pad))
        if not nibble:                 # nibble kernel is single-leaf
            leaf_of_row = jnp.pad(leaf_of_row, (0, stat_pad))

    if nibble:
        return _hist_pallas_nibble(bins, grad, hess, weight, f, n,
                                   num_bins, b_pad, c, fc, interpret,
                                   count_values=count_values)
    f_p, n_p = bins.shape

    stats, acc_dtype = _stats_block(grad, hess, weight, count_values)
    leaf2 = leaf_of_row.astype(jnp.int32)[None, :]       # (1, N_p)

    grid = (f_p // fc, n_p // c)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_leaves=num_leaves,
                          num_bins=b_pad, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((fc, c), lambda fi, ri: (fi, ri)),
            pl.BlockSpec((3, c), lambda fi, ri: (0, ri)),
            pl.BlockSpec((1, c), lambda fi, ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec((3 * num_leaves, fc * b_pad),
                               lambda fi, ri: (0, fi)),
        out_shape=jax.ShapeDtypeStruct(
            (3 * num_leaves, f_p * b_pad), acc_dtype),
        interpret=interpret,
    )(bins, stats, leaf2)

    # (3L, F_p*B_pad) -> (3, L, F, B)
    hist = out.reshape(3, num_leaves, f_p, b_pad)
    if f_p != f or b_pad != num_bins:
        hist = hist[:, :, :f, :num_bins]
    return hist


def _stats_block(grad, hess, weight, count_values):
    """(3, N) stats block + MXU accumulator dtype. Float32 inputs take
    the classic path (bit-identical to HEAD). Integer grad/hess
    (quantized training) keep the block in the narrow wire dtype —
    weight is then the 0/1 row mask and count_values the quantized
    per-row weight — and accumulate exactly in int32."""
    if jnp.issubdtype(grad.dtype, jnp.integer):
        sdt = grad.dtype
        w = weight.astype(sdt)
        cv = w if count_values is None \
            else count_values.astype(sdt) * w
        stats = jnp.stack([grad * w, hess.astype(sdt) * w, cv], axis=0)
        return stats, jnp.int32
    cw = weight if count_values is None else count_values * weight
    stats = jnp.stack([grad * weight, hess * weight, cw],
                      axis=0).astype(jnp.float32)
    return stats, jnp.float32


def _hist_pallas_nibble(bins, grad, hess, weight, f, n, num_bins,
                        b_pad, c, fc, interpret, count_values=None):
    """Single-leaf histogram through the digit-decomposition kernel.
    The tiny per-step VMEM footprint (no (fc*B, C) one-hot block) lets
    row chunks grow to 8192, cutting grid-step count ~8x as well.
    Block geometry comes from _block_plan; inputs arrive already padded
    to the plan's targets by hist_pallas."""
    h, l = _nibble_hl(b_pad)
    f_p, n_p = bins.shape

    stats, acc_dtype = _stats_block(grad, hess, weight, count_values)

    grid = (f_p // fc, n_p // c)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_nibble, h=h, l=l,
                          acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((fc, c), lambda fi, ri: (fi, ri)),
            pl.BlockSpec((3, c), lambda fi, ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec((3 * h, fc * l), lambda fi, ri: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((3 * h, f_p * l), acc_dtype),
        interpret=interpret,
    )(bins, stats)

    # (3h, F_p*l): feature j's bins live at rows (s*h + hi), cols
    # (j*l + lo); bin = hi*l + lo -> one small XLA transpose rebuilds
    # the (3, 1, F, B) contract
    hist = out.reshape(3, h, f_p, l).transpose(0, 2, 1, 3) \
        .reshape(3, 1, f_p, b_pad)
    if f_p != f or b_pad != num_bins:
        hist = hist[:, :, :f, :num_bins]
    return hist
