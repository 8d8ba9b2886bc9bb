"""The expert layer that the decoder families share (``latent_moe_lm``,
``hybrid_moe_lm``), and the small pieces both build from: RMSNorm, a
matrix product in the operands' dtype, the gated (SwiGLU) feed-forward.

An expert layer routes over all ``experts_total`` experts (sigmoid
scores and a score-correction bias that enters the choice only, or,
by ``scoring_func`` and ``use_expert_bias``, a softmax over every
expert and no bias), and
computes the part of the result that the ``experts_held`` experts of
rank ``expert_rank`` give, plus ``n_shared_experts`` shared experts. A
chip that holds every expert is ``experts_held == experts_total``,
rank 0. No token is dropped: the (token, expert) pairs routed here go
through the grouped products in passes. Where a share of the experts is
held, the passes are as many as the pairs routed here take, and each
runs all three products and adds its rows into their tokens (a
scatter-add). Where every expert is held, every pair is routed here:
the passes are ``pairs / rows`` whatever the router chose and run what
needs passes (the gate and up products, which read their rows through
the token ids: no gathered input), the sorted
order is a permutation of the pairs, so the down product runs once a
layer over every pair and its output returns to the tokens by the
order's inverse: a gather and a sum of k (``combines_by_gather``).

The layer reads its sizes from a ``cfg`` with these attributes:
``hidden_size``, ``moe_intermediate_size``, ``experts_total``,
``experts_held``, ``expert_rank``, ``num_experts_per_tok``,
``n_shared_experts``, ``routed_scaling_factor``, ``gate_norm_eps`` (what
is added to the sum of the chosen scores before the gates are divided
by it; 0 in GLM's family, 1e-6 in LFM2's) and ``dtype``; and, where
it has them, ``scoring_func`` ("sigmoid", the default, or "softmax") and
``use_expert_bias`` (true by default; false: no ``router_bias``
parameter, and the scores alone decide the choice).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax

from mmlspark_tpu.ops.grouped_matmul import (grouped_matmul, grouped_swiglu,
                                             row_table)

_F32 = jnp.float32

# rows of the routed experts' input gathered at a time, as a share of
# the mean a step routes here: one pass takes the mean and a margin, and
# a layer routed more than that here takes as many passes as it needs,
# so no token is dropped
PASS_SHARE = 1.25
# ... and no more rows than this, whatever the share: a pass's sorted
# rows cover ``rows / pairs`` of the experts, whose weight blocks the
# grouped kernels fetch once a pass. Where a share is held a pass holds
# its rows' gathered input (a pass of every pair of a step would be
# 131,072 rows of 2048 at LFM2's widths: 0.54 GB) and the float32
# gate, up and down products (three ``grouped_matmul``). Where every
# expert is, the rows are never gathered: the kernel that multiplies
# them out (``grouped_swiglu``) reads each through its token id from a
# copy of the layer's input in 32-bit words made once (``row_table``), and
# gate and up never leave that kernel: a pass
# writes its rows of silu(gate) * up in the model's dtype into the
# layer's buffer (131,072 x 1536 bfloat16: 0.40 GB) and the down
# product is no pass's: one call over every pair, whose float32 output
# (131,072 x 2048: 1.07 GB) the combine reads
PASS_ROWS_MAX = 32768


def combines_by_gather(held: int, total: int) -> bool:
    """Whether ``routed_experts`` returns the experts' outputs to their
    tokens by a gather: where every expert is held, because only then
    are the rows routed here a permutation of the (token, slot) pairs.
    A share of the experts sees a few of the pairs (``held / total`` of
    them), and a gather over every pair would read far more rows than
    its scatter-add writes."""
    return held == total


def gather_combines(cfg, expert_layers: int) -> int:
    """How many of a module's ``expert_layers`` combine by the gather
    (and run their down product once a layer and a pass's gate, up and
    silu * up as one kernel that reads the pass's rows through their
    token ids, the same branch): what the families expose as
    ``moe_gather_combines``, ``moe_layer_down_products``,
    ``moe_fused_swiglu_layers`` and ``moe_row_fetch_layers``, and
    ``TPUModel.metrics()`` carries."""
    every = combines_by_gather(cfg.experts_held, cfg.experts_total)
    return expert_layers if every else 0


def _fan_in(fan_in: int):
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, _F32)
                * fan_in ** -0.5).astype(dtype)
    return init


def _ones(key, shape, dtype):
    return jnp.ones(shape, dtype)


def rms_norm(x, scale, eps: float):
    xf = x.astype(_F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * scale.astype(_F32)


def _mm(expr, a, b, out=None):
    """A matrix product in the operands' dtype, float32 accumulation."""
    y = jnp.einsum(expr, a, b.astype(a.dtype),
                   preferred_element_type=_F32)
    return y if out is None else y.astype(out)


def swiglu(u, gate, up, down):
    h = jax.nn.silu(_mm("tk,kn->tn", u, gate)) * _mm("tk,kn->tn", u, up)
    return _mm("tn,nk->tk", h.astype(u.dtype), down)


class GatedMLP(nn.Module):
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        dim = c.hidden_size
        gate = self.param("gate", _fan_in(dim), (dim, self.width), c.dtype)
        up = self.param("up", _fan_in(dim), (dim, self.width), c.dtype)
        down = self.param("down", _fan_in(self.width),
                          (self.width, dim), c.dtype)
        return swiglu(u, gate, up, down)


class ExpertLayer(nn.Module):
    """Routes over all ``experts_total``; computes the experts held here
    and the shared experts. Returns (y, chosen (t, k), load (held,))."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        dim, width, held = (c.hidden_size, c.moe_intermediate_size,
                            c.experts_held)
        router = self.param("router", _fan_in(dim),
                            (c.experts_total, dim), c.dtype)
        bias = self.param("router_bias", nn.initializers.normal(0.02),
                          (c.experts_total,), _F32) \
            if getattr(c, "use_expert_bias", True) else None
        w_gate = self.param("experts_gate", _fan_in(dim),
                            (held, dim, width), c.dtype)
        w_up = self.param("experts_up", _fan_in(dim),
                          (held, dim, width), c.dtype)
        w_down = self.param("experts_down", _fan_in(width),
                            (held, width, dim), c.dtype)
        with jax.named_scope("moe_route"):
            chosen, gates = route(u, router, bias, c.num_experts_per_tok,
                                  c.routed_scaling_factor, c.gate_norm_eps,
                                  getattr(c, "scoring_func", "sigmoid"))
        with jax.named_scope("moe_experts"):
            y, load = routed_experts(u, chosen, gates, w_gate, w_up,
                                     w_down, c.expert_rank * held,
                                     c.experts_total)
        if c.n_shared_experts:
            with jax.named_scope("moe_shared"):
                for i in range(c.n_shared_experts):
                    y = y + GatedMLP(c, width, name=f"shared_{i}")(u)
        return y.astype(u.dtype), chosen, load


SCORING = {"sigmoid": jax.nn.sigmoid,
           "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}


def route(u, router, bias, k: int, scaling: float, norm_eps: float = 0.0,
          scoring: str = "sigmoid"):
    """Scores over every expert in float32 (each logit's sigmoid, or
    the softmax over all of them); the k largest of score + bias are
    chosen (of the score alone where ``bias`` is None), and the chosen
    scores (without the bias), normalised over the k (``norm_eps``
    added to their sum) and scaled, are the gates."""
    logits = jnp.einsum("td,ed->te", u.astype(_F32), router.astype(_F32),
                        precision=lax.Precision.HIGHEST)
    scores = SCORING[scoring](logits)
    _, chosen = lax.top_k(
        scores if bias is None else scores + bias[None, :], k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(picked, -1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    gates = scaling * picked / total
    return chosen, gates


def routed_experts(u, chosen, gates, w_gate, w_up, w_down, first: int,
                   total: int):
    """The part of sum_i g_i E_i(u) that the experts [first, first +
    held) give. u (t, dim); chosen, gates (t, k). The (token, expert)
    pairs routed here are sorted by expert and go through the grouped
    products in passes of ``_pass_rows`` rows.

    A share of the experts (``held < total``): as many passes as the
    pairs routed here take (one unless the experts held are popular),
    each running gate, up and down, scaling its rows by their gates and
    adding them into their tokens. Every expert (``combines_by_gather``):
    ``pairs / rows`` passes, each writing its rows of silu(gate) * up,
    in ``u``'s dtype, into its slice of one (passes * rows, width)
    buffer that nothing fills first (the passes write every row of
    it); after the last, one down product over every pair, whose
    float32 output is the layer's buffer as it stands (no pass zeroes
    it, copies into it or fills it first: every row up to the pairs is
    some group's, and nothing reads a row past them); then, token by
    token, the k rows of the token are gathered through the inverse of
    the sorted order, scaled and summed in float32, slot 0 first."""
    t, k = chosen.shape
    held = w_gate.shape[0]
    every = combines_by_gather(held, total)
    local = (chosen - first).reshape(-1)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum((key[:, None] == jnp.arange(held)[None, :]
                    ).astype(jnp.int32), axis=0)
    ends = jnp.cumsum(load)
    n_here = ends[-1]
    rows = _pass_rows(t * k, held, total)
    passes = -(-t * k // rows)
    pad = passes * rows - t * k
    token_of = jnp.pad(order // k, (0, pad))
    if not every:
        gate_of = jnp.pad(gates.reshape(-1)[order], (0, pad))

    def one_pass(i, acc):
        lo = i * rows
        tok = lax.dynamic_slice_in_dim(token_of, lo, rows)
        if not every:
            gate = lax.dynamic_slice_in_dim(gate_of, lo, rows)
        sizes = jnp.clip(ends - lo, 0, rows) \
            - jnp.clip(ends - load - lo, 0, rows)
        # the grouped products apart from the sort, gather, scaling and
        # combine around them (``moe_dispatch_share`` reads the rest).
        # Where every expert is held, gate, up and silu * up are one
        # call that reads the pass's rows of ``u`` through their token
        # ids (no gathered copy of them) and writes its rows into slice
        # ``lo`` of the buffer; a row of no group (past the pairs, in a
        # last pass not full: token 0's) is left as found: it feeds
        # only the same row of the down product, which is of no group
        # either
        if every:
            with jax.named_scope("moe_grouped"):
                return grouped_swiglu(table, w_gate, w_up, sizes, acc, lo,
                                      tok=tok)
        x = u[tok]
        with jax.named_scope("moe_grouped"):
            h = jax.nn.silu(grouped_matmul(x, w_gate, sizes, _F32)) \
                * grouped_matmul(x, w_up, sizes, _F32)
            out = grouped_matmul(h.astype(u.dtype), w_down, sizes, _F32)
        live = (lo + jnp.arange(rows)) < n_here
        out = jnp.where(live[:, None], out * gate[:, None], 0.0)
        return acc.at[tok].add(out)

    # (every pair is here where every expert is: ``passes`` trips)
    trips = (n_here + rows - 1) // rows
    if not every:
        return lax.fori_loop(0, trips, one_pass,
                             jnp.zeros((t, u.shape[1]), _F32)), load
    # the rows the passes read through their ids, in the form the kernel
    # reads them from, made once for every pass (``u`` itself off the
    # chip). Every row of the buffer is some pass's, so it starts as it
    # is found (zeros off the chip)
    table = row_table(u, rows)
    acc = lax.fori_loop(0, trips, one_pass, lax.empty(
        (passes * rows, w_gate.shape[2]), u.dtype))
    with jax.named_scope("moe_grouped"):
        out_all = grouped_matmul(acc, w_down, load, _F32, rest_unread=True)
    with jax.named_scope("moe_combine"):
        return _gather_combine(out_all, order, gates), load


def _gather_combine(out_all, order, gates):
    """y[token] = sum_j gates[token, j] * out_all[inv[token * k + j]],
    slot 0 first, in float32. ``order`` is a permutation of the t * k
    pairs (row p of ``out_all`` is pair ``order[p]``'s) and ``inv`` its
    inverse; rows of ``out_all`` past the pairs (a last pass not full)
    are never read. A slot at a time, so that the (t, k, dim) gathered
    rows never exist at once."""
    t, k = gates.shape
    inv = jnp.argsort(order).reshape(t, k)
    y = gates[:, 0, None] * out_all[inv[:, 0]]
    for j in range(1, k):
        y = y + gates[:, j, None] * out_all[inv[:, j]]
    return y


def _pass_rows(pairs: int, held: int, total: int) -> int:
    """Rows a pass takes: ``PASS_SHARE`` of the mean number of pairs
    routed here, a multiple of 512 (the kernel's row tile), no more than
    the pairs there are and no more than ``PASS_ROWS_MAX``; 0 where
    there is no expert."""
    if not total:
        return 0
    want = int(pairs * held / total * PASS_SHARE)
    if want >= 512:
        return min(-(-want // 512) * 512, -(-pairs // 512) * 512,
                   PASS_ROWS_MAX)
    return min(max(8, -(-want // 8) * 8), -(-pairs // 8) * 8)


def _row_loads(chosen, first: int, held: int):
    """(b, held) float32: the (token, expert) pairs of each row that
    fall to each expert held here."""
    local = chosen - first
    return jnp.sum((local[:, :, None] == jnp.arange(held)[None, None, :]
                    ).astype(_F32), axis=1)
