"""Pallas TPU flash attention (dense, single-device path).

The O(L^2) score matrix of ``ring_attention.attention`` never leaves
VMEM here: the kernel streams K/V blocks past each Q block, maintaining
online-softmax statistics (m, l, acc) in scratch across the KV grid
axis — O(L) HBM traffic per head instead of materializing (L, L) scores
(the standard TPU flash-attention scheme; same m/l/o algebra the ring
layer uses across devices, applied within one device).

Same contract as ring_attention.attention: q (B, Lq, H, D),
k/v (B, Lk, H, D), optional causal masking with global position offsets
(shards of a longer sequence). Rows whose keys are all masked return 0,
matching the ring layer's _finalize.

Grid: (B*H, Lq blocks, Lk blocks) with the KV axis innermost — TPU grid
steps run sequentially, so VMEM scratch carries the running statistics
and the output block is written once, on the last KV step. In causal
mode, KV blocks entirely above the diagonal skip their matmuls
(roughly 2x fewer FLOPs at long L).

The BACKWARD is also Pallas (O(L) memory): the forward additionally
writes the per-row log-sum-exp, and two kernels recompute the
probabilities blockwise — one accumulating dQ across KV blocks, one
accumulating dK/dV across Q blocks (the standard split used because TPU
grid steps are sequential: each kernel's scratch accumulator matches its
innermost axis). Long-context training therefore never materializes the
(L, L) score matrix in either direction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# measured on v5e (H=8-16, D=64-128, causal fwd+bwd): 1024x1024 blocks
# run ~2x faster than the 256x256 default at every L from 1k to 32k —
# fewer grid steps and fewer online-softmax rescales per KV element.
# The backward's (bq, bk) f32 intermediates need the VMEM of v5e+ parts.
BLOCK_Q = 1024
BLOCK_K = 1024

# block ceiling at D <= 128 by jax ``device_kind``, exact match. A TPU
# that is not listed is an error, not a guess: add it here once the
# kernel has been compiled on it (chip_smoke.py does).
_BLOCK_CAP_BY_KIND = {
    "TPU v5 lite": 1024,     # v5e
}


def _block_caps(d: int):
    """Per-generation, per-head-dim block ceiling: the tuned 1024 blocks
    are VMEM-safe on v5e+ up to D=128 (measured); D=160 overflows the
    16 MB scoped-vmem limit in the backward (observed: 16.78M request),
    so wider heads halve the blocks. Off-TPU the kernel only runs
    interpreted (tests), where 256 keeps the interpreter quick."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return 256, 256
    cap = _BLOCK_CAP_BY_KIND.get(dev.device_kind)
    if cap is None:
        raise ValueError(
            f"flash_attention has no block sizes for device_kind "
            f"{dev.device_kind!r}; add it to _BLOCK_CAP_BY_KIND in "
            f"ops/flash_attention.py (known: "
            f"{sorted(_BLOCK_CAP_BY_KIND)})")
    if d > 128:
        cap = min(cap, 512)
    return min(BLOCK_Q, cap), min(BLOCK_K, cap)


def _fully_masked(qi, ki, bq, bk, q_offset, k_offset):
    """True when KV block ki is entirely above Q block qi's diagonal."""
    return (ki * bk + k_offset) > (qi * bq + (bq - 1) + q_offset)


def _valid_mask(qi, ki, bq, bk, causal, q_offset, k_offset, lk_true):
    kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < lk_true
    if causal:
        qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid = valid & (qpos + q_offset >= kpos + k_offset)
    return valid


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, q_offset: int, k_offset: int,
                lq_true: int, lk_true: int, bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

        # mask: padding keys always; causal by global positions
        valid = _valid_mask(qi, ki, bq, bk, causal, q_offset, k_offset,
                            lk_true)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[:]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked-so-far rows keep m at NEG_INF; shift by m_new only
        # where finite so exp() never sees inf-inf
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # (bq, bk)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        # skip KV blocks entirely above the diagonal — the scratch
        # statistics are untouched, exactly as if the block contributed
        # nothing (which it would have)
        pl.when(jnp.logical_not(
            _fully_masked(qi, ki, bq, bk, q_offset, k_offset)))(body)
    else:
        body()

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # per-row logsumexp for the backward; fully-masked rows keep
        # NEG_INF (their p recomputes as 0 via the same valid mask)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool, q_offset: int,
               k_offset: int, lk_true: int, bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        g = g_ref[0].astype(jnp.float32)                  # (bq, D)
        lse = lse_ref[0]                                  # (bq, 1)
        delta = dlt_ref[0]                                # (bq, 1)

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        valid = _valid_mask(qi, ki, bq, bk, causal, q_offset, k_offset,
                            lk_true)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)        # (bq, bk)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # (bq, bk)
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(jnp.logical_not(
            _fully_masked(qi, ki, bq, bk, q_offset, k_offset)))(body)
    else:
        body()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, g_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, q_offset: int, k_offset: int, lk_true: int,
                bq: int, bk: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        g = g_ref[0].astype(jnp.float32)                  # (bq, D)
        lse = lse_ref[0]                                  # (bq, 1)
        delta = dlt_ref[0]                                # (bq, 1)

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        valid = _valid_mask(qi, ki, bq, bk, causal, q_offset, k_offset,
                            lk_true)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)        # (bq, bk)
        # padded Q rows carry g == 0 and delta == 0, so their p rows
        # cancel out of both accumulations — no extra masking needed
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bk, D)
        dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # (bq, bk)
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bk, D)

    if causal:
        pl.when(jnp.logical_not(
            _fully_masked(qi, ki, bq, bk, q_offset, k_offset)))(body)
    else:
        body()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _blocks(lq, lk, d):
    cap_q, cap_k = _block_caps(d)
    bq = min(cap_q, max(8, lq + ((-lq) % 8)))
    bk = min(cap_k, max(128, lk + ((-lk) % 128)))
    return bq, bk, (-lq) % bq, (-lk) % bk


def _lse_pad(lq: int, d: int) -> int:
    """Padded Q length of the forward's lse output — callers that
    fabricate lse-shaped tensors (ring_flash_attention's masked hop)
    must match it, so derive it from _blocks rather than restating the
    block-size formula."""
    _, _, pad_q, _ = _blocks(lq, lq, d)
    return lq + pad_q


def _heads_major(x, pad, lpad_idx=1):
    """(B, L, H, D) -> (B*H, L(+pad), D)."""
    b, l, h, d = x.shape
    xt = x.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0)))
    return xt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, q_offset, k_offset, interpret):
    out, _ = _flash_forward(q, k, v, causal, q_offset, k_offset, interpret)
    return out


def _flash_fwd(q, k, v, causal, q_offset, k_offset, interpret):
    out, lse = _flash_forward(q, k, v, causal, q_offset, k_offset,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, k_offset, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, q_offset,
                           k_offset, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, q_offset: int = 0,
                    k_offset: int = 0, interpret: bool = False):
    """Drop-in for ring_attention.attention on big blocks.
    Differentiable with O(L) memory in BOTH directions: the forward saves
    the per-row logsumexp and the custom_vjp backward recomputes
    probabilities blockwise in two Pallas kernels (dQ; dK/dV)."""
    return _flash(q, k, v, bool(causal), int(q_offset), int(k_offset),
                  bool(interpret))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "k_offset", "interpret"))
def _flash_forward(q, k, v, causal: bool = False, q_offset: int = 0,
                   k_offset: int = 0, interpret: bool = False):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / float(d) ** 0.5
    bq, bk, pad_q, pad_k = _blocks(lq, lk, d)

    # heads-major (BH, L, D) layout for per-(batch, head) grid blocks
    qt = _heads_major(q, pad_q)
    kt = _heads_major(k, pad_k)
    vt = _heads_major(v, pad_k)

    grid = (b * h, (lq + pad_q) // bq, (lk + pad_k) // bk)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, q_offset=q_offset,
            k_offset=k_offset, lq_true=lq, lk_true=lk, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            # (1, bq, 1) keeps Mosaic's tiling rule: bq % 8 == 0 and the
            # minor block dim equals the array's minor dim
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq + pad_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, lq + pad_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)

    out = out[:, :lq].reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype), lse


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "k_offset", "interpret"))
def _flash_backward(q, k, v, out, lse, g, causal, q_offset, k_offset,
                    interpret):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / float(d) ** 0.5
    bq, bk, pad_q, pad_k = _blocks(lq, lk, d)

    qt = _heads_major(q, pad_q)
    kt = _heads_major(k, pad_k)
    vt = _heads_major(v, pad_k)
    gt = _heads_major(g, pad_q)     # padded rows are zero -> no dK/dV leak
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term
    delta = jnp.sum(gt.astype(jnp.float32)
                    * _heads_major(out, pad_q).astype(jnp.float32),
                    axis=-1, keepdims=True)
    # lse already (BH, Lq+pad, 1) from the forward

    kw = dict(scale=scale, causal=causal, q_offset=q_offset,
              k_offset=k_offset, lk_true=lk, bq=bq, bk=bk)
    nq, nk_blocks = (lq + pad_q) // bq, (lk + pad_k) // bk

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(b * h, nq, nk_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq + pad_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, gt, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(b * h, nk_blocks, nq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk + pad_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, lk + pad_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(kt, vt, qt, gt, lse, delta)

    def _back(x, l):
        return x[:, :l].reshape(b, h, l, d).transpose(0, 2, 1, 3)

    return _back(dq, lq), _back(dk, lk), _back(dv, lk)
