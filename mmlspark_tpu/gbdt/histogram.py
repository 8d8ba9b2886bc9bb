"""Histogram building — the GBDT hot loop, on device.

The reference's hot loop is LightGBM's native histogram construction with
a socket allreduce between workers per iteration
(ref: src/lightgbm/src/main/scala/TrainUtils.scala:82-89 — distributed
sync happens inside ``LGBM_BoosterUpdateOneIter``). Here the histogram is
an XLA program and the allreduce is ``lax.psum`` over the mesh's data
axis — riding ICI instead of ethernet sockets.

The binned matrix is FEATURES-MAJOR, (F, N) int32: rows (the reduction
dim) live in the TPU lane dimension, per-feature reads are contiguous,
and the Pallas kernel consumes the layout without a transpose. Whether
the bins were assigned on host (BinMapper.transform*) or on device
(binning.bucketize_fm_device — the f32-safe ingest path), the layout
and bin semantics here are identical; these kernels never see the
difference.

Three device strategies, one contract:
  - 'pallas': VMEM-resident bin one-hot contracted on the MXU — the TPU
    production path (see pallas_hist.py).
  - 'scatter': segment_sum scatter-add. The CPU-backend default;
    hundreds of times slower than the matmul paths on TPU.
  - 'onehot': stats×one-hot einsum over row chunks via lax.scan —
    portable fallback; round-trips the one-hot through HBM.

Output layout: (3, L, F, B) — channels grad / hess / count, L leaf
slots, F features, B bins. Float32 in the default path; quantized
training (tree.py hist_bits < 32) feeds integer grad/hess/count values
and gets exact int32 accumulators back — the Shi et al. (NeurIPS'22)
quantized-histogram recipe, where the f32 work moves to a single
dequantize at split-gain time. Integer histograms additionally ride the
collective on a NARROW wire (``wire_dtype=int16``): the global-L1
gradient scaling in tree.py bounds every partial sum by the quantization
range, so the 2x-narrower psum payload cannot overflow.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def build_histogram(bins: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                    weight: jnp.ndarray, leaf_of_row: jnp.ndarray,
                    num_leaves: int, num_bins: int,
                    method: str = "scatter",
                    axis_name: Optional[str] = None,
                    true_shape=None,
                    count_values: Optional[jnp.ndarray] = None,
                    wire_dtype=None) -> jnp.ndarray:
    """Per-(leaf, feature, bin) sums of grad/hess/count.

    bins: (F, N) int32 features-major; grad/hess/weight: (N,) f32;
    leaf_of_row: (N,) int32. weight doubles as the padding/bagging mask
    (0 = row ignored). Returns (3, L, F, B) f32, psum'd over
    ``axis_name`` when given. ``true_shape`` (pallas only) marks bins
    pre-padded to the kernel's block multiples — see
    pallas_hist.padded_bins_shape.

    Quantized mode (tree.py hist_bits < 32): grad/hess arrive as
    stochastically-rounded integers, ``weight`` is the 0/1 row mask, and
    ``count_values`` carries the quantized per-row weight for the count
    channel (None keeps the classic c = Σ weight). Accumulation is then
    exact int32. ``wire_dtype`` (e.g. int16) narrows the collective:
    the histogram is cast down for the psum and widened back — safe
    because the global-L1 scales bound every partial sum (see
    tree.grow_tree's quantization contract).
    """
    if true_shape is not None and method != "pallas":
        raise ValueError(
            "true_shape (pre-padded bins) is a pallas-only contract; "
            f"method={method!r} would return phantom padded features")
    if method == "onehot":
        if count_values is not None:
            raise ValueError(
                "quantized histograms (hist_bits < 32) are not supported "
                "by hist_method='onehot' (its einsum accumulates f32); "
                "use hist_method='scatter' or 'pallas'")
        hist = _hist_onehot(bins, grad, hess, weight, leaf_of_row,
                            num_leaves, num_bins)
    elif method == "pallas":
        from mmlspark_tpu.gbdt.pallas_hist import hist_pallas
        hist = hist_pallas(
            bins, grad, hess, weight, leaf_of_row, num_leaves, num_bins,
            interpret=jax.default_backend() != "tpu",
            true_shape=true_shape, count_values=count_values)
    else:
        hist = _hist_scatter(bins, grad, hess, weight, leaf_of_row,
                             num_leaves, num_bins,
                             count_values=count_values)
    if axis_name is not None:
        if wire_dtype is not None and \
                jnp.issubdtype(hist.dtype, jnp.integer):
            hist = lax.psum(hist.astype(wire_dtype), axis_name) \
                .astype(jnp.int32)
        else:
            hist = lax.psum(hist, axis_name)
    return hist


def _hist_scatter(bins, grad, hess, weight, leaf_of_row,
                  num_leaves, num_bins, count_values=None):
    f, n = bins.shape
    lfb = num_leaves * f * num_bins
    # flat segment id per (feature, row): ((leaf * F) + f) * B + bin
    seg = (leaf_of_row[None, :] * f
           + jnp.arange(f)[:, None]) * num_bins + bins
    seg = seg.reshape(-1)

    def one(values):
        # integer stats (quantized mode) accumulate in int32 — the
        # narrow per-row products widen BEFORE the segment reduction
        if jnp.issubdtype(values.dtype, jnp.integer):
            values = values.astype(jnp.int32)
        v = jnp.broadcast_to(values[None, :], (f, n)).reshape(-1)
        return jax.ops.segment_sum(v, seg, num_segments=lfb,
                                   indices_are_sorted=False)

    g = one(grad * weight)
    h = one(hess * weight)
    c = one(weight if count_values is None else count_values * weight)
    return jnp.stack([g, h, c]).reshape(3, num_leaves, f, num_bins)


def _hist_onehot(bins, grad, hess, weight, leaf_of_row,
                 num_leaves, num_bins, chunk: int = 4096):
    f, n = bins.shape
    x = f * num_bins
    pad = (-n) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        grad = jnp.pad(grad, (0, pad))
        hess = jnp.pad(hess, (0, pad))
        weight = jnp.pad(weight, (0, pad))  # pad rows weight 0 → no effect
        leaf_of_row = jnp.pad(leaf_of_row, (0, pad))
    steps = (n + pad) // chunk
    bins_c = bins.reshape(f, steps, chunk).transpose(1, 0, 2)  # (S, F, C)
    grad_c = grad.reshape(steps, chunk)
    hess_c = hess.reshape(steps, chunk)
    w_c = weight.reshape(steps, chunk)
    leaf_c = leaf_of_row.reshape(steps, chunk)

    def body(acc, args):
        b, g, h, w, l = args                                  # b: (F, C)
        stats = jnp.stack([g * w, h * w, w], axis=0)          # (3, C)
        leaf_oh = jax.nn.one_hot(l, num_leaves,
                                 dtype=jnp.float32)            # (C, L)
        lhs = stats[:, None, :] * leaf_oh.T[None, :, :]        # (3, L, C)
        bin_oh = jax.nn.one_hot(b, num_bins, dtype=jnp.float32)  # (F, C, B)
        rhs = bin_oh.transpose(1, 0, 2).reshape(chunk, x)      # (C, F*B)
        contrib = jnp.einsum(
            "slc,cx->slx", lhs, rhs,
            preferred_element_type=jnp.float32)                # (3, L, X)
        return acc + contrib, None

    init = jnp.zeros((3, num_leaves, x), dtype=jnp.float32)
    acc, _ = lax.scan(body, init, (bins_c, grad_c, hess_c, w_c, leaf_c))
    return acc.reshape(3, num_leaves, f, num_bins)
