"""Pallas TPU flash attention (dense, single-device path).

The O(L^2) score matrix of ``ring_attention.attention`` never leaves
VMEM here: the kernel streams K/V blocks past each Q block, maintaining
online-softmax statistics (m, l, acc) across the KV grid axis — O(L)
HBM traffic per head instead of materializing (L, L) scores (the
standard TPU flash-attention scheme; same m/l/o algebra the ring layer
uses across devices, applied within one device).

Same contract as ring_attention.attention: q (B, Lq, H, D),
k/v (B, Lk, H, D), optional causal masking with global position offsets
(shards of a longer sequence). Rows whose keys are all masked return 0,
matching the ring layer's _finalize.

Grouped-query attention: k/v may hold fewer heads, (B, Lk, H_kv, D)
with H a multiple of H_kv; key/value head h // (H / H_kv) serves query
head h. The grid still runs over the B*H query heads and the K/V block
specs read the shared head by its index, so no repeated copy of K and V
exists in HBM. In the backward each query head's dK/dV part is written
on its own (float32) and the parts of a group are added up outside the
kernel. H_kv == H is the call as it always was.

What is fetched and what is computed are two sizes. The grid is
(B*H, Lq blocks, Lk blocks) with the KV axis innermost, and a grid step
FETCHES one block of up to 1024 rows of each operand: one DMA an
operand and head, few grid steps (a step costs more than a small
product). Inside a step the body COMPUTES in tiles (128 queries x 256
keys at the tuned blocks), and ``tile_plan`` says which: a tile wholly
above the diagonal, or wholly of padded keys, is not visited; a tile
wholly below it runs without the mask (no iotas, compares or selects);
only a tile the diagonal or the end of the keys crosses builds
``_valid_mask``. At L <= 1024 one fetch block is the whole sequence, so
all the skipping there is between tiles (20 of 32 run, 8 of them
masked), not between grid blocks; at longer L a fetch block wholly above
the diagonal has no tile to run. Everything but a block's indices is
known at trace time, so the tiles are unrolled, straight-line code the
compiler schedules as one block (a loop over tiles with traced bounds
ran 1.4 x slower than no tiles at all). TPU grid steps run sequentially,
so VMEM scratch carries the running statistics between KV blocks and
the output block is written once, on the last KV step.

A sliding WINDOW (``window``: query p sees keys j with 0 <= p - j <
window) is a third bound beside padding and the diagonal, and the one
that changes the grid: a query block needs only the key blocks of its
band, so a windowed call's key axis counts the steps of that band (2 at
1024-row blocks and a window of 1024, whatever L) and the K/V index maps
offset each step by the query block's band start. A block outside the
band is neither fetched nor stepped over (skipping it under ``pl.when``
would still cost a grid step and a fetch each). Forward only: the
backward kernels below do not know the window and a windowed call's
gradient is refused. A call without a window is, to the jaxpr, the call
it was before the window existed.

The BACKWARD is also Pallas (O(L) memory): the forward additionally
writes the per-row log-sum-exp, and two kernels recompute the
probabilities tile by tile under the same plan — one accumulating dQ
per query tile across key tiles, one accumulating dK/dV per key tile
across query tiles (the standard split used because TPU grid steps are
sequential: each kernel's scratch accumulator matches its innermost
axis). Long-context training therefore never materializes the (L, L)
score matrix in either direction.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# FETCH blocks. Measured on v5e (H=8-16, D=64-128, causal fwd+bwd):
# 1024x1024 grid blocks run ~2x faster than 256x256 grid blocks at every
# L from 1k to 32k — fewer grid steps, each of which costs more than a
# small product. That compared grid blocks; the compute tiles inside a
# fetch block (_tile) pay no grid step.
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


def _blocks(lq, lk, d):
    cap_q, cap_k = _block_caps(d)
    bq = min(cap_q, max(8, lq + ((-lq) % 8)))
    bk = min(cap_k, max(128, lk + ((-lk) % 128)))
    return bq, bk, (-lq) % bq, (-lk) % bk


# COMPUTE tiles inside a fetch block, chosen on v5e among 64-512 a side
# at L = 1024, D = 64 by the three kernels' times (PERF.md, PR 33): a
# query tile is the stretch of rows that shares one walk over the keys
# (128: more, shorter walks overlap better in the forward, whose row
# maximum has to be known before its exponentials start); a key tile is
# the width at which the diagonal is followed and the dK/dV kernel walks
# the queries (256: at 128 its transposed products double).
TILE_Q = 128
TILE_K = 256


def _tile(block: int, want: int) -> int:
    """The compute tile of a fetch block: ``want`` rows where the block
    holds two or more such tiles, else half of that, else the block
    itself (a block that is a multiple of neither is one tile)."""
    for t in (want, want // 2):
        if block % t == 0 and block >= 2 * t:
            return t
    return block


def _count(x: int, t: int, n: int) -> int:
    """floor(x / t) held to [0, n]: how many whole tiles of t lie
    under x."""
    return min(max(x, 0), n * t) // t


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Which compute tiles of which fetch block run, and how. The only
    place that knows: the three kernels and the tests read the same
    methods, and the tests hold the answer to a dense boolean mask.

    A fetch block's tiles follow from two numbers, its *kind*: ``shift``,
    the position of its first query less that of its first key (held to
    the range in which it decides anything), and ``real``, how many of
    its keys are not padding. Everything but the block's indices is a
    Python int at trace time, so a call has a handful of kinds (one at
    L <= 1024; below, on and above the diagonal at longer L, each with
    or without padded keys) and a kernel holds one unrolled body a
    kind. Padded QUERY rows are not the plan's business (they are
    computed and sliced away, as ever).

    ``window`` (0: none) is a third bound beside padding and the
    diagonal: query p sees key j only where ``0 <= p - j < window``. A
    tile then has masked key tiles at both ends of its walk
    (``key_start``), and a query block needs the key blocks of its
    *band* alone (``band``): a windowed call's grid runs over the band,
    ``band_blocks`` steps a query block, not over every key block."""
    lq: int
    lk: int
    bq: int
    bk: int
    tq: int
    tk: int
    causal: bool
    q_offset: int
    k_offset: int
    window: int = 0

    @property
    def grid(self):
        """(query fetch blocks, key fetch blocks)."""
        return -(-self.lq // self.bq), -(-self.lk // self.bk)

    def band(self, qi):
        """(first, last) key fetch block that query block qi needs under
        the window: from the block of the oldest key its first query
        sees to the block of its last query's own position, held to the
        grid. Python ints for an int, traced scalars for a program id;
        last < first where the block sees no key at all."""
        ints = isinstance(qi, int)
        lo = qi * self.bq + self.q_offset - self.k_offset
        hi = lo + self.bq - 1
        lo = lo - (self.window - 1)
        nk = self.grid[1]
        if ints:
            return max(lo, 0) // self.bk, min(hi // self.bk, nk - 1)
        return (jnp.maximum(lo, 0) // self.bk,
                jnp.minimum(jnp.maximum(hi, -1) // self.bk, nk - 1))

    @property
    def band_blocks(self) -> int:
        """Grid steps along the keys: the widest band of any query
        block where there is a window, every key block where not."""
        nq, nk = self.grid
        if not self.window:
            return nk
        return max(1, max(hi - lo + 1 for lo, hi in map(
            self.band, range(nq))))

    @property
    def tiles(self):
        """(query tiles, key tiles) of one fetch block."""
        return self.bq // self.tq, self.bk // self.tk

    def block_kind(self, qi, ki):
        """(shift, real) of fetch block (qi, ki): Python ints for the
        plan's own totals and the tests, traced scalars for a kernel,
        whose block indices are program ids."""
        clip = (lambda x, lo, hi: min(max(x, lo), hi)) \
            if isinstance(qi, int) and isinstance(ki, int) else jnp.clip
        real = clip(self.lk - ki * self.bk, 0, self.bk)
        if not self.causal:
            return 0, real
        shift = (qi * self.bq + self.q_offset
                 - ki * self.bk - self.k_offset)
        # at -bq and under no query sees a key, from bk - 1 up all do;
        # under a window none does again from window + bk - 1 up
        top = self.window + self.bk - 1 if self.window else self.bk - 1
        return clip(shift, -self.bq, top), real

    def kinds(self) -> dict:
        """kind -> how many fetch blocks of the call are of it."""
        nq, nk = self.grid
        found: dict = {}
        for qi in range(nq):
            lo, hi = self.band(qi) if self.window else (0, nk - 1)
            for ki in range(lo, hi + 1):
                kind = self.block_kind(qi, ki)
                found[kind] = found.get(kind, 0) + 1
        return found

    def key_span(self, kind, i: int):
        """For query tile i of a block of this kind -> (bare, run): key
        tiles [0, bare) run without the mask, [bare, run) with it, and
        [run, n) are not visited."""
        shift, real = kind
        n = self.bk // self.tk
        run = _count(real + self.tk - 1, self.tk, n)
        bare = _count(real, self.tk, n)
        if self.causal:
            gap = shift + i * self.tq   # the tile's first query sees 0..gap
            run = min(run, _count(gap + self.tq - 1 + self.tk, self.tk, n))
            bare = min(bare, _count(gap + 1, self.tk, n))
        return min(bare, run), run

    def key_start(self, kind, i: int):
        """For query tile i of a block of this kind -> (start, clear):
        under a window key tiles [0, start) are not visited (too old for
        every query of the tile) and [start, clear) run with the mask;
        (0, 0) without one."""
        if not self.window:
            return 0, 0
        n = self.bk // self.tk
        # the first key the tile's first query sees; its last query's
        # first key lies tq - 1 further on
        first = kind[0] + i * self.tq - (self.window - 1)
        if first >= kind[1]:            # ... and that is past the keys
            return n, n
        return (_count(first, self.tk, n),
                _count(first + self.tq - 1 + self.tk - 1, self.tk, n))

    def query_span(self, kind, j: int):
        """For key tile j of a block of this kind -> (first, bare):
        query tiles [0, first) are not visited, [first, bare) run with
        the mask, and [bare, n) without it."""
        shift, real = kind
        n = self.bq // self.tq
        first = bare = 0
        if self.causal:
            gap = j * self.tk - shift   # the tile's first key is seen by gap..
            first = _count(gap, self.tq, n)
            bare = _count(gap + self.tk - 1 + self.tq - 1, self.tq, n)
        if j * self.tk >= real:                 # nothing but padding
            first = n
        if (j + 1) * self.tk > real:            # some padding
            bare = n
        return first, bare

    def runs(self, kind) -> bool:
        """Whether a block of this kind has any tile to run (without a
        window the last query tile sees the most; under one any may)."""
        return any(self.key_span(kind, i)[1] > self.key_start(kind, i)[0]
                   for i in range(self.bq // self.tq))

    def tile_kind(self, kind, i: int, j: int) -> str:
        bare, run = self.key_span(kind, i)
        start, clear = self.key_start(kind, i)
        if j < start or j >= run:
            return "skipped"
        return "bare" if clear <= j < bare else "masked"

    def counts(self) -> dict:
        """Totals over every fetch block: tiles_run / tiles_square is
        the share of the square's products and element-wise work that
        is executed (20 / 32 for a causal 1024 x 1024 in 128 x 256
        tiles)."""
        n_i, n_j = self.tiles
        nq, nk = self.grid
        # the fetch blocks of one (row, head): those with a tile to run,
        # and the grid steps spent on them and on the others
        total = {"tiles_square": nq * nk * n_i * n_j, "tiles_run": 0,
                 "tiles_masked": 0, "blocks_run": 0,
                 "blocks_grid": nq * self.band_blocks}
        for kind, blocks in self.kinds().items():
            tiles = [self.tile_kind(kind, i, j)
                     for i in range(n_i) for j in range(n_j)]
            masked = tiles.count("masked")
            total["tiles_run"] += blocks * (masked + tiles.count("bare"))
            total["tiles_masked"] += blocks * masked
            total["blocks_run"] += blocks * self.runs(kind)
        return total


def tile_plan(lq: int, lk: int, d: int, causal: bool, q_offset: int = 0,
              k_offset: int = 0, window: int = 0) -> TilePlan:
    """The plan of one call, from what the call can observe: lengths,
    head width (through the block caps), ``causal``, the offsets and
    the window (0: none; it narrows a causal call only)."""
    if window < 0 or (window and not causal):
        raise ValueError(f"window={window}: a window is a positive count "
                         f"of keys on a causal call (0 for none)")
    bq, bk, _, _ = _blocks(lq, lk, d)
    return TilePlan(lq, lk, bq, bk, _tile(bq, TILE_Q), _tile(bk, TILE_K),
                    bool(causal), int(q_offset), int(k_offset), int(window))


def _valid_mask(plan: TilePlan, kind, r0: int, nr: int, c0: int, nc: int):
    """(nr, nc) mask of query rows r0.. against key rows c0.. of a
    block of this kind (block-relative): padding keys always; causal
    and the window by position. Only the comparisons that can fail in
    there are built."""
    shift, real = kind
    col = lax.broadcasted_iota(jnp.int32, (nr, nc), 1)
    valid = None
    if real - c0 < nc:
        valid = col < real - c0
    gap = shift + r0 - c0               # row r sees columns 0..r+gap
    if plan.causal and gap < nc - 1:
        seen = lax.broadcasted_iota(jnp.int32, (nr, nc), 0) + gap >= col
        valid = seen if valid is None else valid & seen
    if plan.window and nr - 1 + gap >= plan.window:
        # ... and none at or under column r + gap - window
        near = lax.broadcasted_iota(jnp.int32, (nr, nc), 0) \
            + (gap - plan.window) < col
        valid = near if valid is None else valid & near
    return valid


def _each_kind(plan: TilePlan, qi, ki, body):
    """``body(kind)`` once for every kind of fetch block that has a tile
    to run, each under the ``pl.when`` that picks it by the program's
    block indices. A block with no tile to run (wholly above the
    diagonal) leaves the scratch untouched, exactly as if it had
    contributed nothing (which it would have)."""
    shift, real = plan.block_kind(qi, ki)
    for kind in plan.kinds():
        if plan.runs(kind):
            pl.when((shift == kind[0]) & (real == kind[1]))(
                functools.partial(body, kind))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _mask_from(x, start: int, valid, fill, stop=None):
    """x with its columns from ``start`` on (up to ``stop``, where
    given) set to ``fill`` where not valid; the other columns are not
    touched."""
    if stop is None or stop == x.shape[1]:
        tail = jnp.where(valid, x[:, start:], fill)
        return tail if start == 0 else jnp.concatenate(
            [x[:, :start], tail], axis=1)
    parts = [x[:, :start], jnp.where(valid, x[:, start:stop], fill),
             x[:, stop:]]
    return jnp.concatenate(parts[start == 0:], axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, plan: TilePlan, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    tq, tk = plan.tq, plan.tk

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def block(kind):
        # Every query tile's scores and row maximum first, then their
        # exponentials: a tile's maximum has to be known before its
        # exponentials start, and in this order the next tile's product
        # fills that wait.
        scores = {}
        for i in range(plan.bq // tq):
            bare, run = plan.key_span(kind, i)
            start, clear = plan.key_start(kind, i)
            if run <= start:
                continue
            rows = pl.ds(i * tq, tq)
            # every key the tile sees here
            keys = pl.ds(start * tk, (run - start) * tk)
            q = q_ref[0, rows, :].astype(jnp.float32) * scale   # (tq, D)
            s = _dot(q, k_ref[0, keys, :].astype(jnp.float32), _NT)
            # the key tiles that build the mask: [bare, run) at the
            # diagonal or the end of the keys, [start, clear) at the
            # window's edge, one stretch where both are in the walk
            lo = start if clear > start else max(bare, start)
            hi = run if run > bare else min(clear, run)
            valid = _valid_mask(plan, kind, i * tq, tq, lo * tk,
                                (hi - lo) * tk) if hi > lo else None
            span = ((lo - start) * tk, (hi - start) * tk)
            if valid is not None:
                s = _mask_from(s, span[0], valid, NEG_INF, span[1])
            m_prev = m_scr[rows]                                # (tq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            scores[i] = (rows, keys, span, s, valid, m_prev, m_new)
        for rows, keys, span, s, valid, m_prev, m_new in scores.values():
            p = jnp.exp(s - m_new)                              # (tq, keys)
            if valid is not None:
                # fully-masked-so-far rows keep m at NEG_INF, where
                # exp(s - m) is exp(0) for every masked key
                p = _mask_from(p, span[0], valid, 0.0, span[1])
            corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            l_scr[rows] = l_scr[rows] * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_scr[rows] = acc_scr[rows] * corr + _dot(
                p, v_ref[0, keys, :].astype(jnp.float32), _NN)
            m_scr[rows] = m_new

    # under a window the grid's key axis counts the steps of the query
    # block's band, not the key blocks
    _each_kind(plan, qi, plan.band(qi)[0] + ki if plan.window else ki,
               block)

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # per-row logsumexp for the backward; fully-masked rows keep
        # NEG_INF (their p recomputes as 0 via the same valid mask)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, dq_ref,
               dq_scr, *, plan: TilePlan, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    tq, tk = plan.tq, plan.tk

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def block(kind):
        for i in range(plan.bq // tq):
            bare, run = plan.key_span(kind, i)
            if not run:
                continue
            rows = pl.ds(i * tq, tq)
            keys = pl.ds(0, run * tk)       # every key the tile sees here
            q = q_ref[0, rows, :].astype(jnp.float32) * scale   # (tq, D)
            g = g_ref[0, rows, :].astype(jnp.float32)           # (tq, D)
            k = k_ref[0, keys, :].astype(jnp.float32)           # (keys, D)
            v = v_ref[0, keys, :].astype(jnp.float32)           # (keys, D)
            p = jnp.exp(_dot(q, k, _NT) - lse_ref[0, rows, :])  # (tq, keys)
            if run > bare:
                p = _mask_from(p, bare * tk, _valid_mask(
                    plan, kind, i * tq, tq, bare * tk, (run - bare) * tk),
                    0.0)
            ds = p * (_dot(g, v, _NT) - dlt_ref[0, rows, :])
            dq_scr[rows] = dq_scr[rows] + _dot(ds, k, _NN)

    _each_kind(plan, qi, ki, block)

    @pl.when(ki == nk - 1)
    def _():
        # ds lacks the scores' scale until here: once a row, not once
        # a score
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, g_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, plan: TilePlan,
                scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    tq, tk = plan.tq, plan.tk
    n_i = plan.bq // tq

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def block(kind):
        for j in range(plan.bk // tk):
            first, bare = plan.query_span(kind, j)
            if first == n_i:
                continue
            cols = pl.ds(j * tk, tk)
            k = k_ref[0, cols, :].astype(jnp.float32) * scale   # (tk, D)
            v = v_ref[0, cols, :].astype(jnp.float32)           # (tk, D)
            dk, dv = dk_scr[cols], dv_scr[cols]
            # the queries the diagonal crosses, then those wholly under it
            for lo, hi, masked in ((first, bare, True), (bare, n_i, False)):
                if hi == lo:
                    continue
                r0, nr = lo * tq, (hi - lo) * tq
                rows = pl.ds(r0, nr)
                q = q_ref[0, rows, :].astype(jnp.float32)       # (nr, D)
                g = g_ref[0, rows, :].astype(jnp.float32)       # (nr, D)
                p = jnp.exp(_dot(q, k, _NT) - lse_ref[0, rows, :])
                if masked:
                    p = jnp.where(_valid_mask(plan, kind, r0, nr,
                                              j * tk, tk), p, 0.0)
                # padded Q rows carry g == 0 and delta == 0, so their p
                # rows cancel out of both accumulations — no extra
                # masking needed
                dv = dv + _dot(p, g, _TN)                       # (tk, D)
                ds = p * (_dot(g, v, _NT) - dlt_ref[0, rows, :])
                dk = dk + _dot(ds, q, _TN)                      # (tk, D)
            dk_scr[cols], dv_scr[cols] = dk, dv

    _each_kind(plan, qi, ki, block)

    @pl.when(qi == nq - 1)
    def _():
        # ds lacks the scores' scale until here, as in _dq_kernel
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _lse_pad(lq: int, d: int) -> int:
    """Padded Q length of the forward's lse output — callers that
    fabricate lse-shaped tensors (ring_flash_attention's masked hop)
    must match it, so derive it from _blocks rather than restating the
    block-size formula."""
    _, _, pad_q, _ = _blocks(lq, lq, d)
    return lq + pad_q


def _kv_group(q, k, v) -> int:
    """Query heads a key/value head serves: H / H_kv, 1 for plain
    multi-head attention."""
    h, hk = q.shape[2], k.shape[2]
    if v.shape[2] != hk or h % hk:
        raise ValueError(
            f"flash_attention needs k and v with the same number of "
            f"heads, a divisor of q's: q has {h}, k {hk}, v {v.shape[2]}")
    return h // hk


def _kv_block(bk: int, d: int, group: int, key_axis: int, plan=None):
    """BlockSpec of a K or V fetch block: the grid's first index is the
    query head's b*H + h, whose key/value head is b*H_kv + h // group =
    (b*H + h) // group (the index itself, with no arithmetic, where
    group is 1). ``key_axis`` says which of the grid's other two indices
    counts key blocks. With a windowed ``plan`` (the forward's) that
    index counts the steps of the query block's band instead: step j
    fetches the band's j-th key block, and a step past the band's end
    names the last block again, which is no new fetch."""
    def index(bh, i, j):
        if plan is not None and plan.window:
            first, last = plan.band(i)
            j = jnp.maximum(jnp.minimum(first + j, last), 0)
        return (bh if group == 1 else bh // group,
                i if key_axis == 1 else j, 0)
    return pl.BlockSpec((1, bk, d), index)


def _heads_major(x, pad, lpad_idx=1):
    """(B, L, H, D) -> (B*H, L(+pad), D)."""
    b, l, h, d = x.shape
    xt = x.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0)))
    return xt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, q_offset, k_offset, interpret, window):
    out, _ = _flash_forward(q, k, v, causal, q_offset, k_offset, interpret,
                            window)
    return out


def _flash_fwd(q, k, v, causal, q_offset, k_offset, interpret, window):
    out, lse = _flash_forward(q, k, v, causal, q_offset, k_offset,
                              interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, k_offset, interpret, window, res, g):
    if window:
        raise NotImplementedError(
            f"flash_attention(window={window}) has no backward: the dQ "
            f"and dK/dV kernels do not know the window (a windowed layer "
            f"scores; it does not train yet)")
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, q_offset,
                           k_offset, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, q_offset: int = 0,
                    k_offset: int = 0, interpret: bool = False,
                    window: int = 0):
    """Drop-in for ring_attention.attention on big blocks.
    Differentiable with O(L) memory in BOTH directions: the forward saves
    the per-row logsumexp and the custom_vjp backward recomputes
    probabilities blockwise in two Pallas kernels (dQ; dK/dV).

    ``window`` (0: none) narrows a causal call to the ``window`` newest
    keys of each query, itself among them: the forward's grid then runs
    over each query block's band of key blocks alone. Forward only: the
    gradient of a windowed call is refused."""
    return _flash(q, k, v, bool(causal), int(q_offset), int(k_offset),
                  bool(interpret), int(window))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "k_offset", "interpret",
                     "window"))
def _flash_forward(q, k, v, causal: bool = False, q_offset: int = 0,
                   k_offset: int = 0, interpret: bool = False,
                   window: int = 0):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    group = _kv_group(q, k, v)
    scale = 1.0 / float(d) ** 0.5
    bq, bk, pad_q, pad_k = _blocks(lq, lk, d)
    plan = tile_plan(lq, lk, d, causal, q_offset, k_offset, window)

    # heads-major (BH, L, D) layout for per-(batch, head) grid blocks
    qt = _heads_major(q, pad_q)
    kt = _heads_major(k, pad_k)
    vt = _heads_major(v, pad_k)

    # (under a window the key axis is the band: plan.band_blocks steps)
    grid = (b * h, (lq + pad_q) // bq, plan.band_blocks)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            _kv_block(bk, d, group, 2, plan),
            _kv_block(bk, d, group, 2, plan),
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
    group = _kv_group(q, k, v)
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

    kw = dict(plan=tile_plan(lq, lk, d, causal, q_offset, k_offset),
              scale=scale)
    nq, nk_blocks = (lq + pad_q) // bq, (lk + pad_k) // bk

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(b * h, nq, nk_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            _kv_block(bk, d, group, 2),
            _kv_block(bk, d, group, 2),
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
            _kv_block(bk, d, group, 1),
            _kv_block(bk, d, group, 1),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        # a group's query heads each write their part of dK and dV
        # (float32, added up below); without groups a head's part is
        # the whole
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lk + pad_k, d),
                                 k.dtype if group == 1 else jnp.float32),
            jax.ShapeDtypeStruct((b * h, lk + pad_k, d),
                                 v.dtype if group == 1 else jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(kt, vt, qt, gt, lse, delta)

    def _back(x, l):
        return x[:, :l].reshape(b, h, l, d).transpose(0, 2, 1, 3)

    if group == 1:
        return _back(dq, lq), _back(dk, lk), _back(dv, lk)

    def _back_kv(x, like):
        parts = x[:, :lk].reshape(b, h // group, group, lk, d)
        return parts.sum(axis=2).transpose(0, 2, 1, 3).astype(like.dtype)

    return _back(dq, lq), _back_kv(dk, k), _back_kv(dv, v)
