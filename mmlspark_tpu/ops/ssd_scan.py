"""The selective scan of a Mamba-2 layer by the chunked SSD algorithm
(Dao & Gu 2024, "Transformers are SSMs", section 6), in ``jax.numpy``.

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
accumulate in float32; the decays, the states and the sums are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
    float32, the final states (batch, H, P, N) float32)."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads over {groups} groups")
    r, op = heads // groups, x.dtype
    pad = -length % chunk
    if pad:
        def padded(v):
            return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = padded(x), padded(dt), padded(b), padded(c)
    nc = (length + pad) // chunk
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
