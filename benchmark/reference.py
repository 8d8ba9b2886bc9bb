"""The plain reference: GPT-2 as this repo's configurations state it, in
``jax.numpy`` float32 with every matmul at highest precision, written
from the equations. It imports nothing of the program and reads only a
parameter tree:

    embed/embedding (V, d), pos_embed (max_len, d),
    block_i/{ln1, ln2}/{scale, bias}, block_i/{qkv, proj, mlp_up,
    mlp_down}/{kernel, bias}, ln_f/{scale, bias},
    lm_head | head /{kernel, bias}

Pre-LN blocks, LayerNorm eps 1e-6, fused QKV with bias, causal softmax
attention scaled by 1/sqrt(head size), tanh GELU, learned positions, an
untied head with bias; a classifier head reads the mean token. Training
is the mean over rows of the mean token cross-entropy, and AdamW as
optax states it (b1 0.9, b2 0.999, eps 1e-8, decay on every leaf).

It runs layer by layer and in blocks of rows so that it fits beside
nothing else on one chip. The controls are the same equations in the
precision below bfloat16 that would tempt a later change:
``matmul="int8"`` rounds Dense kernels to 8-bit integers by output
channel and their activations under one scale a tensor, as the
program's own ``TPUModel.quantize`` path does; ``matmul="fp8"`` rounds
both operands of every matmul to float8 e4m3 under one scale a tensor
(what fp8 training multiplies in).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _fake_int8(x, axis):
    scale = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    # straight-through: the rounding has no gradient of its own
    return x + jax.lax.stop_gradient(q * scale - x)


def _fake_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa, exponents down to
    2**-6, subnormals below, largest 448) under one scale a tensor that
    puts its largest entry at 448: the format fp8 training multiplies
    in. Written as arithmetic so that it needs no fp8 type."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    xs = x / scale
    exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(xs), 2.0 ** -6)))
    step = jnp.exp2(exp - 3)
    q = jnp.clip(jnp.round(xs / step) * step, -448.0, 448.0)
    return x + jax.lax.stop_gradient(q * scale - x)


def _einsum(expr, a, b, matmul, weights=False):
    """``weights`` marks a Dense layer's matmul (activation x kernel).
    The int8 control follows the program's own int8 path
    (core/quantize.py): Dense kernels by output channel, their
    activations under one scale a tensor, attention left as it is."""
    if matmul == "int8" and weights:
        a, b = _fake_int8(a, None), _fake_int8(b, 0)
    elif matmul == "fp8":
        a, b = _fake_fp8(a), _fake_fp8(b)
    return jnp.einsum(expr, a, b, precision=_HI)


def _dense(x, p, matmul):
    return _einsum("...k,kn->...n", x, p["kernel"], matmul, True) \
        + p["bias"]


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] \
        + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, heads, matmul="f32"):
    b, s, d = x.shape
    hd = d // heads
    qkv = _dense(_layer_norm(x, p["ln1"]), p["qkv"], matmul)
    q, k, v = (t.reshape(b, s, heads, hd) for t in jnp.split(qkv, 3, -1))
    scores = _einsum("bqhd,bkhd->bhqk", q, k, matmul) / math.sqrt(hd)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    attn = _einsum("bhqk,bkhd->bqhd", probs, v, matmul)
    x = x + _dense(attn.reshape(b, s, d), p["proj"], matmul)
    y = _gelu_tanh(_dense(_layer_norm(x, p["ln2"]), p["mlp_up"], matmul))
    return x + _dense(y, p["mlp_down"], matmul)


def _embed(p_embed, p_pos, tokens):
    return p_embed["embedding"][tokens] + p_pos[None, :tokens.shape[1]]


def _head_name(params):
    return "head" if "head" in params else "lm_head"


def _head_logits(p_lnf, p_head, x, pooled, matmul):
    x = _layer_norm(x, p_lnf)
    if pooled:
        x = x.mean(axis=1)
    return _dense(x, p_head, matmul)


@functools.lru_cache(maxsize=None)
def _jits(heads: int, matmul: str, pooled: bool):
    blk = functools.partial(block, heads=heads, matmul=matmul)

    def block_bwd(p, x, g):
        _, vjp = jax.vjp(blk, p, x)
        return vjp(g)

    def head_loss(p_lnf, p_head, x, targets, scale):
        logits = _head_logits(p_lnf, p_head, x, pooled, matmul)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        # mean over tokens, then over rows: `scale` is 1 / rows of the
        # whole batch, so that blocks of rows add up
        return ((lse - picked).mean(axis=-1)).sum() * scale

    def embed_bwd(tokens, g, vocab, max_len):
        d = g.shape[-1]
        g_emb = jnp.zeros((vocab, d), g.dtype).at[tokens.reshape(-1)].add(
            g.reshape(-1, d))
        g_pos = jnp.zeros((max_len, d), g.dtype).at[
            :g.shape[1]].add(g.sum(0))
        return g_emb, g_pos

    return {
        "embed": jax.jit(_embed),
        "block": jax.jit(blk),
        "block_bwd": jax.jit(block_bwd),
        "logits": jax.jit(functools.partial(
            _head_logits, pooled=pooled, matmul=matmul)),
        "head_loss_grad": jax.jit(jax.value_and_grad(
            head_loss, argnums=(0, 1, 2))),
        "embed_bwd": jax.jit(embed_bwd, static_argnums=(2, 3)),
        "add": jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                       donate_argnums=(0,)),
    }


def forward(params, tokens, heads: int, matmul: str = "f32",
            rows_per_block: int = 2) -> np.ndarray:
    """Logits of every row, as float32 on the host."""
    depth = sum(1 for k in params if k.startswith("block_"))
    head = _head_name(params)
    fn = _jits(heads, matmul, head == "head")
    out = []
    tokens = np.asarray(tokens, np.int32)
    for r0 in range(0, len(tokens), rows_per_block):
        x = fn["embed"](params["embed"], params["pos_embed"],
                        jnp.asarray(tokens[r0:r0 + rows_per_block]))
        for i in range(depth):
            x = fn["block"](params[f"block_{i}"], x)
        out.append(np.asarray(fn["logits"](
            params["ln_f"], params[head], x)))
    return np.concatenate(out, axis=0)


def loss_and_grads(params, tokens, targets, heads: int,
                   matmul: str = "f32", rows_per_block: int = 2):
    """Mean over rows of the mean token cross-entropy, and its gradient
    in the tree's own structure, by explicit backpropagation layer by
    layer over blocks of rows."""
    depth = sum(1 for k in params if k.startswith("block_"))
    head = _head_name(params)
    fn = _jits(heads, matmul, False)
    tokens = np.asarray(tokens, np.int32)
    targets = np.asarray(targets, np.int32)
    n = len(tokens)
    vocab, d = params["embed"]["embedding"].shape
    max_len = params["pos_embed"].shape[0]
    total, grads = 0.0, None
    for r0 in range(0, n, rows_per_block):
        tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
        tgt = jnp.asarray(targets[r0:r0 + rows_per_block])
        xs = [fn["embed"](params["embed"], params["pos_embed"], tok)]
        for i in range(depth):
            xs.append(fn["block"](params[f"block_{i}"], xs[-1]))
        loss, (g_lnf, g_head, g) = fn["head_loss_grad"](
            params["ln_f"], params[head], xs.pop(), tgt,
            jnp.float32(1.0 / n))
        part = {"ln_f": g_lnf, head: g_head}
        for i in reversed(range(depth)):
            part[f"block_{i}"], g = fn["block_bwd"](
                params[f"block_{i}"], xs.pop(), g)
        g_emb, part["pos_embed"] = fn["embed_bwd"](tok, g, vocab, max_len)
        part["embed"] = {"embedding": g_emb}
        grads = part if grads is None else fn["add"](grads, part)
        total += float(loss)
    return total, grads


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnums=(5, 6))
def _adamw(params, mu, nu, grads, step, lr, weight_decay):
    def leaf(p, m, v, g):
        m = ADAM_B1 * m + (1 - ADAM_B1) * g
        v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
        m_hat = m / (1 - ADAM_B1 ** step)
        v_hat = v / (1 - ADAM_B2 ** step)
        p = p - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS)
                      + weight_decay * p)
        return p, m, v
    out = jax.tree_util.tree_map(leaf, params, mu, nu, grads)
    pick = lambda i: jax.tree_util.tree_map(          # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict:
    """{'block_0/qkv/kernel': l2 norm, ...} of every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            float(jnp.linalg.norm(jnp.asarray(leaf, jnp.float32).ravel()))
            for path, leaf in flat}


@jax.jit
def _masked_change(after, start, moved):
    return jax.tree_util.tree_map(
        lambda a, b, m: jnp.where(m, a.astype(jnp.float32) - b, 0.0),
        after, start, moved)


def change_norms(after, start, moved) -> dict:
    """Norm of every leaf's change from ``start`` to ``after`` over the
    elements that ``moved`` marks."""
    return leaf_norms(_masked_change(after, start, moved))


@jax.jit
def _at_least(grads, threshold):
    return jax.tree_util.tree_map(lambda g: jnp.abs(g) >= threshold, grads)


def moved_elements(grads):
    """The elements whose gradient is a thousandth or more of the median
    leaf's root-mean-square gradient. The others (a key's bias under
    softmax, the embedding of a token the batch does not hold) have no
    gradient but round-off, and under Adam round-off moves them as far
    as any: they are left out of the norms of change."""
    rms = np.median([float(jnp.sqrt(jnp.mean(g * g)))
                     for g in jax.tree_util.tree_leaves(grads)])
    return _at_least(grads, jnp.float32(1e-3 * rms))


def train_follow(params, batches, heads: int, lr: float,
                 weight_decay: float, matmul: str = "f32",
                 rows_per_block: int = 2, fault: str = "",
                 moved=None) -> dict:
    """Follow AdamW over ``batches`` [(tokens, targets), ...] from
    ``params`` (consumed). Returns each step's loss, ``moved`` (the
    elements that the first gradient moves, unless given) and the norm
    of every leaf's change after the last step over those elements.
    ``fault`` plants what a broken step would do: 'state_unchanged'
    applies no update, 'half_batch' drops the second half of every
    batch and takes the mean over the rest."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, jnp.float32), params)
    start = jax.tree_util.tree_map(jnp.copy, params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t, (tok, tgt) in enumerate(batches, start=1):
        if fault == "half_batch":
            tok, tgt = tok[:len(tok) // 2], tgt[:len(tgt) // 2]
        loss, grads = loss_and_grads(params, tok, tgt, heads, matmul,
                                     rows_per_block)
        losses.append(loss)
        if moved is None:
            moved = moved_elements(grads)
        if fault != "state_unchanged":
            params, mu, nu = _adamw(params, mu, nu, grads,
                                    jnp.float32(t), lr, weight_decay)
        del grads
    return {"losses": losses, "moved": moved,
            "change_norms": change_norms(params, start, moved)}
