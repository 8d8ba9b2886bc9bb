"""Pallas TPU attention over a selected set of keys per query (forward).

``flash_attention`` masks by position alone (causal, offsets). A learned
sparse selector gives every query its own set of keys, the same for all
heads, as an (Lq, Lk) table of 0/1 bytes. This kernel is the same
online-softmax scheme with that table as a fourth operand:

    o[t, h] = sum_{s : keep[t, s]} softmax_s(q[t, h] . k[s, h]) v[s, h]

The set of a causal model lies on and below the diagonal, so KV blocks
entirely above it are skipped and their fetch is redirected to the last
block that is needed (an unchanged block index is not fetched again).
Nothing else is skipped: a trained selector's keys are spread over the
whole prefix. ``heads_per_step`` heads share one fetch of the table's
block. Matrix products take bfloat16 operands and accumulate in
float32; the softmax runs in float32. Inference only: no backward.

Layout is heads-major (B, H, L, D) so that a block is (bq, D) of one
head; a query with an empty set returns 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_Q = 512
BLOCK_K = 512
HEADS_PER_STEP = 4
# the blocks below need ~14 MB with double buffering: over the 16 MB
# that Mosaic grants by default, far under a v5e's 128 MiB
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bk: int, heads: int, causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body():
        keep = keep_ref[0].astype(jnp.int32) != 0            # (bq, bk)
        for g in range(heads):
            s = lax.dot_general(q_ref[0, g], k_ref[0, g],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[g]                                # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a row with nothing kept so far has m at NEG_INF: exp(0)
            # there would count every masked key, so p is masked again
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, g],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    if causal:
        pl.when(ki * bk <= qi * bq + (bq - 1))(body)
    else:
        body()

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)
                    ).astype(o_ref.dtype)


def _block(length: int, want: int) -> int:
    """The largest block under ``want`` that divides the length and
    keeps Mosaic's tiling (a multiple of 128, or the whole axis)."""
    b = min(want, length)
    while b > 128 and length % b:
        b //= 2
    return b if length % b == 0 else length


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _dsa_attend(q, k, v, keep, causal: bool = True,
                interpret: bool = False):
    b, h, lq, d = q.shape
    lk, dv = k.shape[2], v.shape[3]
    bq, bk = _block(lq, BLOCK_Q), _block(lk, BLOCK_K)
    g = HEADS_PER_STEP if h % HEADS_PER_STEP == 0 else 1

    def kv_block(qi, ki):
        # above the diagonal nothing is read: stay on the last block
        return jnp.minimum(ki, (qi * bq + bq - 1) // bk) if causal else ki

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, heads=g, causal=causal),
        grid=(b, h // g, lq // bq, lk // bk),
        in_specs=[
            pl.BlockSpec((1, g, bq, d), lambda b_, hg, qi, ki: (b_, hg, qi, 0)),
            pl.BlockSpec((1, g, bk, d),
                         lambda b_, hg, qi, ki: (b_, hg, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, g, bk, dv),
                         lambda b_, hg, qi, ki: (b_, hg, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, bq, bk),
                         lambda b_, hg, qi, ki: (b_, qi, kv_block(qi, ki))),
        ],
        out_specs=pl.BlockSpec((1, g, bq, dv),
                               lambda b_, hg, qi, ki: (b_, hg, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="dsa_attend",
        interpret=interpret,
    )(q, k, v, keep)


def selected_attention(q, k, v, keep, causal: bool = True,
                       interpret: bool = False):
    """q (B, H, Lq, D), k (B, H, Lk, D), v (B, H, Lk, Dv), q already scaled; ``keep``
    (B, Lq, Lk), nonzero where query t attends to key s, one table for
    all heads. ``causal`` promises that nothing above the diagonal is
    kept, and lets those blocks be skipped."""
    return _dsa_attend(q, k, v, keep.astype(jnp.int8), causal=bool(causal),
                       interpret=bool(interpret))
