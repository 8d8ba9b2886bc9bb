"""Grouped matrix product over the experts a chip holds.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

``lhs`` (m, k) holds the rows sorted by group, group g's rows next to
each other; ``rhs`` (groups, k, n) one matrix a group; ``group_sizes``
(groups,) int32 says how many rows each group has. Their sum may be
less than m: the rows past it belong to no group and come back as
zeros, unless the caller says that nothing reads them
(``rest_unread``): then they hold whatever the kernel left there, and
the pass over the whole output that would zero them is not made. On a
TPU this is the megablox Pallas kernel that ships with jax (its grid
runs over the tiles that hold rows, so the time follows the rows routed
here, not m); elsewhere ``lax.ragged_dot``. bfloat16 operands, float32
accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# m, k and n tile of the kernel: 512 rows amortise an expert's weight
# tile over the rows routed to it, 1024 x 1024 weight tiles stay under
# the default VMEM grant with double buffering. The k and n tiles follow
# the shape (``_tile``): the kernel wants a tile that divides the side
# (a ragged last k tile is masked element by element in every grid
# step), and 1536 is no multiple of 1024
TILING = (512, 1024, 1024)


def _tile(side: int, want: int) -> int:
    """The widest multiple of 128 up to ``want`` that divides ``side``
    (1024 for 2048 and 6144, 768 for 1536); ``side`` itself where it is
    no wider than ``want``, and ``want`` where nothing divides."""
    if side <= want:
        return side
    for t in range(want - want % 128, 0, -128):
        if side % t == 0:
            return t
    return want


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _moe_grouped_matmul(lhs, rhs, group_sizes, out_dtype):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = lhs.shape
    n = rhs.shape[2]
    tiling = (min(TILING[0], m), _tile(k, TILING[1]), _tile(n, TILING[2]))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=tiling)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=jnp.bfloat16, *,
                   rest_unread: bool = False):
    """``rest_unread`` (static) is the caller's word that no row past
    the groups' sum is ever read: a row of the product depends on that
    row of ``lhs`` alone and the kernel's store mask keeps a shared
    tile's foreign rows out, so whatever such a row holds (zeros off
    the chip) reaches nothing the caller does read."""
    group_sizes = group_sizes.astype(jnp.int32)
    m = lhs.shape[0]
    if jax.default_backend() == "tpu" and m % min(TILING[0], m) == 0:
        out = _moe_grouped_matmul(lhs, rhs, group_sizes, out_dtype)
    else:
        out = jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(out_dtype)
    if rest_unread:
        return out
    # rows of no group: the kernel leaves them unwritten
    in_group = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(in_group[:, None], out, jnp.zeros((), out_dtype))
