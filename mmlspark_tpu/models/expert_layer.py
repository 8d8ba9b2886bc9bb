"""The expert layer that the decoder families share (``latent_moe_lm``,
``hybrid_moe_lm``), and the small pieces both build from: RMSNorm, a
matrix product in the operands' dtype, the gated (SwiGLU) feed-forward.

An expert layer routes over all ``experts_total`` experts with sigmoid
scores and a score-correction bias that enters the choice only, and
computes the part of the result that the ``experts_held`` experts of
rank ``expert_rank`` give, plus ``n_shared_experts`` shared experts. A
chip that holds every expert is ``experts_held == experts_total``,
rank 0. No token is dropped: the (token, expert) pairs routed here go
through the grouped products in passes, as many as it takes.

The layer reads its sizes from a ``cfg`` with these attributes:
``hidden_size``, ``moe_intermediate_size``, ``experts_total``,
``experts_held``, ``expert_rank``, ``num_experts_per_tok``,
``n_shared_experts``, ``routed_scaling_factor``, ``gate_norm_eps`` (what
is added to the sum of the chosen scores before the gates are divided
by it; 0 in GLM's family, 1e-6 in LFM2's) and ``dtype``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax

from mmlspark_tpu.ops.grouped_matmul import grouped_matmul

_F32 = jnp.float32

# rows of the routed experts' input gathered at a time, as a share of
# the mean a step routes here: one pass takes the mean and a margin, and
# a layer routed more than that here takes as many passes as it needs,
# so no token is dropped
PASS_SHARE = 1.25
# ... and no more rows than this, whatever the share: a pass holds its
# rows' gathered input and three float32 products (gate, up and down's
# output), which at a chip that holds every expert would be every pair
# of the step at once (131,072 rows x 2048: 3.9 GB at LFM2's widths)
PASS_ROWS_MAX = 32768


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
                          (c.experts_total,), _F32)
        w_gate = self.param("experts_gate", _fan_in(dim),
                            (held, dim, width), c.dtype)
        w_up = self.param("experts_up", _fan_in(dim),
                          (held, dim, width), c.dtype)
        w_down = self.param("experts_down", _fan_in(width),
                            (held, width, dim), c.dtype)
        with jax.named_scope("moe_route"):
            chosen, gates = route(u, router, bias, c.num_experts_per_tok,
                                  c.routed_scaling_factor, c.gate_norm_eps)
        with jax.named_scope("moe_experts"):
            y, load = routed_experts(u, chosen, gates, w_gate, w_up,
                                     w_down, c.expert_rank * held,
                                     c.experts_total)
        if c.n_shared_experts:
            with jax.named_scope("moe_shared"):
                for i in range(c.n_shared_experts):
                    y = y + GatedMLP(c, width, name=f"shared_{i}")(u)
        return y.astype(u.dtype), chosen, load


def route(u, router, bias, k: int, scaling: float, norm_eps: float = 0.0):
    """Sigmoid scores over every expert in float32; the k largest of
    score + bias are chosen, and the chosen scores (without the bias),
    normalised over the k (``norm_eps`` added to their sum) and scaled,
    are the gates."""
    logits = jnp.einsum("td,ed->te", u.astype(_F32), router.astype(_F32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + bias[None, :], k)
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
    products in passes of ``_pass_rows`` rows, as many as it takes (one
    for a share of many experts unless the experts held are popular;
    pairs / PASS_ROWS_MAX where every expert is here)."""
    t, k = chosen.shape
    held = w_gate.shape[0]
    local = (chosen - first).reshape(-1)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum((key[:, None] == jnp.arange(held)[None, :]
                    ).astype(jnp.int32), axis=0)
    ends = jnp.cumsum(load)
    n_here = ends[-1]
    rows = _pass_rows(t * k, held, total)
    pad = -(-t * k // rows) * rows - t * k
    token_of = jnp.pad(order // k, (0, pad))
    gate_of = jnp.pad(gates.reshape(-1)[order], (0, pad))

    def one_pass(i, y):
        lo = i * rows
        tok = lax.dynamic_slice_in_dim(token_of, lo, rows)
        gate = lax.dynamic_slice_in_dim(gate_of, lo, rows)
        sizes = jnp.clip(ends - lo, 0, rows) \
            - jnp.clip(ends - load - lo, 0, rows)
        x = u[tok]
        # the grouped products apart from the sort, gather, scaling and
        # scatter-add around them (``moe_dispatch_share`` reads the rest)
        with jax.named_scope("moe_grouped"):
            h = jax.nn.silu(grouped_matmul(x, w_gate, sizes, _F32)) \
                * grouped_matmul(x, w_up, sizes, _F32)
            out = grouped_matmul(h.astype(u.dtype), w_down, sizes, _F32)
        live = (lo + jnp.arange(rows)) < n_here
        out = jnp.where(live[:, None], out * gate[:, None], 0.0)
        return y.at[tok].add(out)

    y = lax.fori_loop(0, (n_here + rows - 1) // rows, one_pass,
                      jnp.zeros((t, u.shape[1]), _F32))
    return y, load


def _pass_rows(pairs: int, held: int, total: int) -> int:
    """Rows a pass takes: ``PASS_SHARE`` of the mean number of pairs
    routed here, a multiple of 512 (the kernel's row tile), no more than
    the pairs there are and no more than ``PASS_ROWS_MAX``."""
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
