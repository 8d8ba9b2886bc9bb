"""A learned sparse selector's two steps: the index score of every
(query, key) pair and, per query, the exact set of the k largest.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])      (s <= t)
    S_t     = the k largest I[t, s] over s <= t; all of them while t < k

Both run in blocks of queries so that nothing of size Lq x Lk x heads is
held, and a segment of queries only sees the keys up to its end. The
set is exact: the k-th largest score is found bit by bit (a radix
select over the float's ordered bit pattern, 32 counts a block), and
equal scores at the threshold go to the lower positions first, which is
what a stable descending sort gives. The scores' products take bfloat16
operands and accumulate in float32; everything after them is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCORE_BLOCK = 128


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the float's order."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    flip = jnp.where(bits >> 31 != 0, jnp.uint32(0xFFFFFFFF),
                     jnp.uint32(0x80000000))
    return bits ^ flip


def _largest_with_count(ok_at, bits: int, need):
    """The largest unsigned value T of ``bits`` bits, built from the top
    bit down, for which ``ok_at(T)`` counts at least ``need``."""
    def step(i, t):
        cand = t | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(ok_at(cand) >= need, cand, t)
    return lax.fori_loop(0, bits, step, jnp.zeros_like(need, jnp.uint32))


def exact_topk_mask(scores, valid, k: int):
    """``scores`` (n, m) float32, ``valid`` (n, m) bool. True at the k
    largest valid scores of each row (at every valid one where a row has
    k or fewer); ties at the k-th go to the lower columns."""
    n, m = scores.shape
    # valid keys are 1 or more: 0 is kept for what may not be chosen
    key = jnp.where(valid, jnp.maximum(_ordered_bits(scores), 1), 0)
    need = jnp.full((n, 1), k, jnp.int32)

    def count_ge(t):
        return jnp.sum((key >= t).astype(jnp.int32), -1, keepdims=True)
    kth = _largest_with_count(count_ge, 32, need)
    above = key > kth
    ties = (key == kth) & valid
    short = need - jnp.sum(above.astype(jnp.int32), -1, keepdims=True)
    # the ``short`` lowest columns among the ties: the smallest column
    # c with short ties at or before it, found from the top as the
    # largest r = m-1-c with short ties at r or more
    rev = jnp.uint32(m - 1) - lax.broadcasted_iota(jnp.uint32, (n, m), 1)

    def ties_from(r):
        return jnp.sum((ties & (rev >= r)).astype(jnp.int32), -1,
                       keepdims=True)
    last = _largest_with_count(ties_from, max(1, (m - 1).bit_length()),
                               jnp.maximum(short, 1))
    take = ties & (rev >= last) & (short > 0)
    return (above | take) & valid


def index_scores(q_idx, w_idx, k_idx):
    """q_idx (n, J, D), w_idx (n, J) float32 (scales folded in), k_idx
    (m, D): the (n, m) float32 index scores."""
    s = jnp.einsum("njd,md->njm", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_idx[:, :, None], axis=1)


def select_keys(q_idx, w_idx, k_idx, k: int, block: int = SCORE_BLOCK):
    """The selected sets of one sequence as an (L, L) bool table, causal:
    row t is true at the k keys s <= t of largest index score, at all of
    s <= t while t < k. q_idx (L, J, D), w_idx (L, J), k_idx (L, D).

    Queries go in segments of k: segment g sees keys [0, (g+1) k), and
    the first needs no scores at all. Within a segment, ``block``
    queries at a time."""
    length = q_idx.shape[0]
    pos = jnp.arange(length)
    if length <= k:
        return pos[:, None] >= pos[None, :]
    seg = k if length % k == 0 else length
    blk = block if seg % block == 0 else seg
    rows = []
    for start in range(0, length, seg):
        stop = start + seg
        t = pos[start:stop]
        if stop <= k:
            table = t[:, None] >= pos[None, :stop]
        else:
            keys = k_idx[:stop]

            def one(args, keys=keys, stop=stop):
                qb, wb, tb = args
                with jax.named_scope("dsa_score"):
                    score = index_scores(qb, wb, keys)
                with jax.named_scope("dsa_topk"):
                    return exact_topk_mask(
                        score, tb[:, None] >= pos[None, :stop], k)
            n = seg // blk
            table = lax.map(one, (
                q_idx[start:stop].reshape(n, blk, *q_idx.shape[1:]),
                w_idx[start:stop].reshape(n, blk, -1),
                t.reshape(n, blk))).reshape(seg, stop)
        rows.append(jnp.pad(table, ((0, 0), (0, length - stop))))
    return jnp.concatenate(rows, axis=0)
