"""``hybrid_moe_lm``: a decoder whose layers choose their *operator*.

A third network family beside ``networks.Transformer`` and
``latent_moe_lm``: each layer's sequence operator comes from
``layer_types``, one entry a layer, and two kinds are not attention at
all. "conv" is a gated short convolution (a per-channel causal filter of
``conv_L_cache`` taps between two gates and two projections);
"full_attention" is grouped-query attention with a per-head RMSNorm on
queries and keys and rotate-half rotary positions; "sliding_attention"
is the same operator with each query held to its ``sliding_window``
newest keys (itself among them), through the same flash kernel, which
then visits only the band of key blocks a query block needs. Each
attention kind has a rotary table of its own where ``rope_parameters``
names them ("default", or "yarn" with its ``attention_factor`` on cos
and sin, or "none": that kind's layers take no rotary step at all, and
order reaches them through the causal mask and the other kind's
layers); ``head_dim`` is ``hidden_size / num_attention_heads`` unless
the spec gives it. With ``attention_output_gate`` a fifth projection of
the same normed input, through a sigmoid, multiplies the heads' outputs
before the output projection; ``attention_qk_norm`` false drops the
per-head norms, and ``attention_multiplier`` scales the scores in place of
1 / sqrt(head_dim). "mamba" is a Mamba-2 (SSD) layer: a causal conv, a
selective scan over a state of ``mamba_d_state`` a channel computed by
the chunked algorithm of ops/ssd_scan.py, and a gated norm. The first
``num_dense_layers`` layers have a gated (SwiGLU) feed-forward, the
others the expert layer of expert_layer.py (sigmoid scores and a bias
that enters the choice only, or by ``scoring_func`` and
``use_expert_bias`` a softmax over every expert and no bias;
``num_shared_experts`` shared experts beside the routed ones), with
every expert on this chip; with ``num_dense_layers`` the depth there is
no expert layer at all. The field names are those of the published
``config.json`` of the ``lfm2_moe`` family (LFM2-24B-A2B), of the
``mellum`` family (Mellum2-12B-A2.5B), of the ``afmoe`` family
(Trinity-Mini) and of the ``granitemoehybrid`` family
(Granite-4.0-H-Micro).

    h = x + Op_i(RMSNorm(x));  x' = h + FFN_i(RMSNorm(h))
    with ``sandwich_norms``: h = x + RMSNorm(Op_i(RMSNorm(x))), and the
        same around FFN_i: four gains a layer
    x_0 = embed[tokens], times ``embedding_multiplier`` where given, else
        times sqrt(hidden_size) with ``mup_enabled``
    with ``residual_multiplier`` m: h = x + m Op_i(...), x' = h + m FFN_i(...)
    logits = W RMSNorm(x[last]) / logits_scaling
                                    W the tied (vocab, hidden) embedding,
                                    or ``lm_head`` where it is not tied

It is a scorer: token ids (b, l) in, float32 next-token logits of the
last position out. docs/hybrid_moe_lm.md has the equations, the spec
keys, what ``capture`` returns, the scopes and the counters.

Parameters are held in ``dtype`` (bfloat16 behind the server). Matrix
products take ``dtype`` operands and accumulate in float32; norms,
rotary angles, the convolution's gates and taps, router scores, top-k
and softmax are float32, and so are a Mamba-2 layer's conv, step,
decays, states and gated norm.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from mmlspark_tpu.models.expert_layer import (
    SCORING, ExpertLayer, GatedMLP, _F32, _fan_in, _mm, _ones, _pass_rows,
    _row_loads, gather_combines, rms_norm)

Dtype = Any
OPERATORS = ("conv", "full_attention", "sliding_attention", "mamba")
ATTENTION = OPERATORS[1:3]
# the Mamba-2 mixer's sizes, each required where a layer is "mamba"
MAMBA_SIZES = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
               "mamba_n_groups", "mamba_d_conv", "mamba_expand",
               "mamba_chunk_size")
ROPE_TYPES = ("default", "yarn", "none")
# positions at a row's end whose chosen experts ride out of the step
# (``routed_tail``): a choice at position t reaches the last position's
# logits through the later layers' convolutions, two positions a layer,
# so with up to 7 of them after an expert layer the last 15 can; every
# earlier choice reaches it through attention alone, one key in l
ROUTED_TAIL = 16
# ... and positions at a row's end whose attention outputs ride out
# beside them (``attention_tail``), every attention layer's: what a
# comparison holds the q and k norms and the grouping of heads by, out
# of the execution that gave the logits
ATTENTION_TAIL = 4


@dataclasses.dataclass(frozen=True)
class HybridMoEConfig:
    """The sizes of one ``hybrid_moe_lm``, as ``networkSpec`` names them
    (docs/hybrid_moe_lm.md). The defaults are LFM2-24B-A2B's published
    widths with one period of its layers."""

    vocab_size: int = 65536
    max_len: int = 8192
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv",
                                    "conv", "conv")
    num_dense_layers: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    gate_norm_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    # a head's width where it is not hidden_size / num_attention_heads
    head_dim: Optional[int] = None
    # keys a "sliding_attention" layer's query sees, itself among them
    sliding_window: int = 0
    # {attention kind: {"rope_type": "default" | "yarn" | "none",
    # "rope_theta", and for yarn "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "attention_factor"}}; a kind that is not named turns by
    # ``rope_theta``, rope_type "default"; "none" takes no rotary step
    rope_parameters: Any = None
    tie_word_embeddings: bool = True
    scoring_func: str = "sigmoid"
    use_expert_bias: bool = True
    # a fifth projection W_g of the operator's input: sigmoid(W_g u)
    # multiplies the heads' outputs before W_o
    attention_output_gate: bool = False
    # a branch's output is normed before the residual add as well
    sandwich_norms: bool = False
    # the embedding times sqrt(hidden_size) (and drawn at variance
    # 1 / hidden_size, so that it enters the first norm at variance 1)
    mup_enabled: bool = False
    # shared experts of width moe_intermediate_size beside the routed
    # ones, added unweighted
    num_shared_experts: int = 0
    # a per-head RMSNorm on queries and keys before the rotary step
    attention_qk_norm: bool = True
    # the scores' scale where it is not 1 / sqrt(head_dim): folded into
    # q as attention_multiplier * sqrt(head_dim), the kernel's own
    # 1 / sqrt(head_dim) left as it is
    attention_multiplier: Optional[float] = None
    # the embedding times this (mup_enabled's sqrt(hidden_size) where it
    # is not given), drawn at variance 1 / hidden_size where either is
    embedding_multiplier: Optional[float] = None
    # x + residual_multiplier * branch, for both branches of a layer
    residual_multiplier: float = 1.0
    # the logits divided by this
    logits_scaling: float = 1.0
    # a "mamba" layer's Mamba-2 mixer, under the names of the published
    # configs (granitemoehybrid): inner width mamba_expand * hidden_size
    # in mamba_n_heads heads of mamba_d_head, a state of mamba_d_state a
    # channel, B and C shared by the heads of each of mamba_n_groups
    # groups, a causal conv of mamba_d_conv taps, the chunked scan at
    # mamba_chunk_size positions; each is required where layer_types
    # holds "mamba". The two bias keys are read as published: a bias on
    # the conv and none on the projections is the one form built
    mamba_n_heads: Optional[int] = None
    mamba_d_head: Optional[int] = None
    mamba_d_state: Optional[int] = None
    mamba_n_groups: Optional[int] = None
    mamba_d_conv: Optional[int] = None
    mamba_expand: Optional[int] = None
    mamba_chunk_size: Optional[int] = None
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; a "
                             f"layer's operator is one of {OPERATORS}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads: not a "
                f"whole group a head")
        if self.head_dim is None:
            if self.hidden_size % self.num_attention_heads:
                raise ValueError("hidden_size is no multiple of the heads")
            object.__setattr__(
                self, "head_dim",
                self.hidden_size // self.num_attention_heads)
        if self.head_dim <= 0 or self.head_dim % 2:
            raise ValueError(f"head_dim {self.head_dim}: rotate-half "
                             f"pairs need an even, positive width")
        if "sliding_attention" in self.layer_types \
                and self.sliding_window <= 0:
            raise ValueError("a sliding_attention layer needs "
                             "sliding_window > 0")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("a Mamba-2 mixer is built with a bias on its "
                             "conv and none on its projections")
        missing = [k for k in MAMBA_SIZES if getattr(self, k) is None]
        if "mamba" in self.layer_types and missing:
            raise ValueError(f"a mamba layer needs {missing}")
        if "mamba" in self.layer_types and (
                self.mamba_n_heads * self.mamba_d_head
                != self.mamba_expand * self.hidden_size
                or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(
                f"{self.mamba_n_heads} mamba heads of {self.mamba_d_head} "
                f"in {self.mamba_n_groups} groups: not the inner width "
                f"{self.mamba_expand} x {self.hidden_size} in whole groups")
        if self.scoring_func not in SCORING:
            raise ValueError(f"scoring_func {self.scoring_func!r}; one "
                             f"of {sorted(SCORING)}")
        # a JSON object by attention kind -> hashable, checked
        tables = dict(self.rope_parameters or {})
        for kind, table in tables.items():
            table = dict(table)
            if kind not in ATTENTION or table.get(
                    "rope_type", "default") not in ROPE_TYPES:
                raise ValueError(
                    f"rope_parameters[{kind!r}] = {table}: an attention "
                    f"kind of {ATTENTION}, rope_type one of {ROPE_TYPES}")
            tables[kind] = tuple(sorted(table.items()))
        object.__setattr__(self, "rope_parameters",
                           tuple(sorted(tables.items())))

    # what the shared expert layer reads, under its names: every expert
    # is on this chip (there is no all-to-all to combine a share)
    experts_total = experts_held = property(lambda self: self.num_experts)
    expert_rank = 0
    n_shared_experts = property(lambda self: self.num_shared_experts)

    def rope_for(self, kind: str) -> dict:
        """The rotary table of an attention kind's layers."""
        return {"rope_type": "default", "rope_theta": self.rope_theta,
                **dict(dict(self.rope_parameters).get(kind, ()))}

    def flash_blocks(self, kind: str) -> int:
        """Fetch blocks with a tile to run that one (row, head) of a
        ``kind`` layer's flash call visits at ``max_len`` (the kernel's
        own count, ``TilePlan.counts()``); 0 where no layer is of that
        kind."""
        if kind not in self.layer_types:
            return 0
        window = self.sliding_window if kind == "sliding_attention" else 0
        return _blocks_run(self.max_len, self.head_dim, window)


@functools.lru_cache(maxsize=None)
def _blocks_run(length: int, head_dim: int, window: int) -> int:
    # (a scrape of ``TPUModel.metrics()`` asks every time: counted once)
    from mmlspark_tpu.ops.flash_attention import tile_plan
    return tile_plan(length, length, head_dim, True,
                     window=window).counts()["blocks_run"]


def yarn_table(d: int, rope_theta: float, factor: float,
               original_max_position_embeddings: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0, attention_factor=None, **_):
    """YaRN's (inverse frequencies (d/2,) float32, factor on cos and
    sin). Pair i turns ``beta`` times over the original context at
    i = d ln(original / (beta 2 pi)) / (2 ln theta): pairs up to
    ``low`` (``beta_fast``'s, rounded down) keep their frequency, pairs
    from ``high`` (``beta_slow``'s, rounded up) have it divided by
    ``factor``, and those between go linearly from the one to the
    other. ``attention_factor`` is 0.1 ln(factor) + 1 where not given."""
    def turns(beta):
        return d * math.log(original_max_position_embeddings
                            / (beta * 2 * math.pi)) / (2 * math.log(
                                rope_theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), d - 1)
    base = rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 - ramp) * base + ramp * base / factor
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(attention_factor)


def rope_rotate_half(x, positions, theta: float, inv=None,
                     factor: float = 1.0):
    """Rotate the pairs (x[i], x[i + d/2]) of the last axis by
    positions * theta**(-2i/d), or by positions * ``inv[i]`` where a
    table gives the frequencies (``yarn_table``), cos and sin then
    times ``factor``. x (b, l, h, d) float32, positions (l,)."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = positions.astype(_F32)[:, None] * inv[None, :]       # (l, d/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def short_conv(g, taps):
    """c[t] = sum_j taps[j] * g[t - (L - 1) + j] a channel, zeros before
    t = 0: the last tap weighs the present. g (b, l, dim) float32,
    taps (L, dim)."""
    n, length = taps.shape[0], g.shape[1]
    past = jnp.pad(g, ((0, 0), (n - 1, 0), (0, 0)))
    taps = taps.astype(_F32)
    return sum(taps[j] * past[:, j:j + length] for j in range(n))


class ShortConv(nn.Module):
    """[B, C, z] = split3(W_in u); Op = W_out (C * conv(B * z))."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        dim, dt = c.hidden_size, c.dtype
        w_in = self.param("in_proj", _fan_in(dim), (dim, 3 * dim), dt)
        taps = self.param("conv", _fan_in(c.conv_L_cache),
                          (c.conv_L_cache, dim), dt)
        w_out = self.param("out_proj", _fan_in(dim), (dim, dim), dt)
        with jax.named_scope("short_conv"):
            bcz = _mm("bld,de->ble", u, w_in)
            with jax.named_scope("short_conv_gate"):
                gate_b, gate_c, z = jnp.split(bcz, 3, axis=-1)
                y = (gate_c * short_conv(gate_b * z, taps)).astype(dt)
            return _mm("bld,de->ble", y, w_out, dt)


def _conv_bias(fan_in: int):
    """A conv layer's default bias: uniform within 1 / sqrt(fan in)."""
    def init(key, shape, dtype):
        bound = fan_in ** -0.5
        return jax.random.uniform(key, shape, _F32, -bound,
                                  bound).astype(dtype)
    return init


def _a_log(key, shape, dtype):
    """log a for a drawn uniformly from [1, 16] (A = -a)."""
    return jnp.log(jax.random.uniform(key, shape, _F32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniformly from [1e-3,
    1e-1], so that softplus(0 + dt_bias) starts there."""
    step = jnp.exp(jax.random.uniform(key, shape, _F32, math.log(1e-3),
                                      math.log(1e-1)))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 (SSD) layer's operator, H heads of P channels, a state
    of N a channel, G groups sharing B and C:

        [z | xBC | dt] = W_in u
        xBC = silu(conv(xBC) + b_conv)      causal, depthwise (short_conv)
        [x | B | C] = xBC
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        Op = W_out RMSNorm(y * silu(z))     a norm over each group's channels

    by the chunked scan of ops/ssd_scan.py at ``mamba_chunk_size``.
    Scopes: ``ssm_mixer`` around it all, ``ssm_conv`` (the conv, its
    silu and the cast of xBC to ``dtype``), ``ssm_scan`` (the split of
    xBC, the step, the scan) and ``ssm_gated_norm`` inside, the two
    projections under ``ssm_mixer`` alone. The conv, the step, the
    decays, the scan's states and sums and the gated norm are float32;
    the scan's products take ``dtype`` operands. Under a jit over
    several devices the scan's kernel runs per shard."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        from mmlspark_tpu.ops.ssd_scan import kernel_runs, ssd_scan
        from mmlspark_tpu.parallel.ring_attention import flash_per_shard
        c = self.cfg
        dim, dt = c.hidden_size, c.dtype
        heads, width, state, groups = (c.mamba_n_heads, c.mamba_d_head,
                                       c.mamba_d_state, c.mamba_n_groups)
        inner = c.mamba_expand * dim
        channels = inner + 2 * groups * state
        w_in = self.param("in_proj", _fan_in(dim),
                          (dim, inner + channels + heads), dt)
        taps = self.param("conv", _fan_in(c.mamba_d_conv),
                          (c.mamba_d_conv, channels), dt)
        conv_bias = self.param("conv_bias", _conv_bias(c.mamba_d_conv),
                               (channels,), dt)
        dt_bias = self.param("dt_bias", _dt_bias, (heads,), dt)
        a_log = self.param("A_log", _a_log, (heads,), dt)
        skip = self.param("D", _ones, (heads,), dt)
        gain = self.param("norm", _ones, (inner,), dt)
        w_out = self.param("out_proj", _fan_in(inner), (inner, dim), dt)
        b, length = u.shape[:2]
        with jax.named_scope("ssm_mixer"):
            zxd = _mm("bld,de->ble", u, w_in)
            z, xbc, step = jnp.split(zxd, [inner, inner + channels], -1)
            with jax.named_scope("ssm_conv"):
                # x, B and C enter the scan in ``dtype``: cast here, whole,
                # so that the conv's one pass ends in a step of this scope
                xbc = jax.nn.silu(short_conv(xbc, taps)
                                  + conv_bias.astype(_F32)).astype(dt)
            with jax.named_scope("ssm_scan"):
                # the split's slices are copies the kernel reads: the
                # scan's own
                x, bb, cc = jnp.split(xbc, [inner, inner + groups * state],
                                      -1)
                step = jax.nn.softplus(step + dt_bias.astype(_F32))
                operands = (x.reshape(b, length, heads, width), step,
                            -jnp.exp(a_log.astype(_F32)),
                            bb.reshape(b, length, groups, state),
                            cc.reshape(b, length, groups, state), skip)

                def scan(x, step, a, bb, cc, skip):
                    return ssd_scan(x, step, a, bb, cc, c.mamba_chunk_size,
                                    skip)
                mesh = jax.sharding.get_abstract_mesh()
                if mesh.size > 1 and not mesh.manual_axes and kernel_runs(
                        heads, width, state, groups, c.mamba_chunk_size):
                    # rows over the data axis, A and D whole
                    y, _ = flash_per_shard(scan, mesh, *operands,
                                           whole=(2, 5))
                else:
                    y, _ = scan(*operands)
            with jax.named_scope("ssm_gated_norm"):
                g = (y.reshape(b, length, inner) * jax.nn.silu(z)).reshape(
                    b, length, groups, inner // groups)
                y = rms_norm(g, gain.reshape(groups, -1),
                             c.norm_eps).reshape(b, length, inner)
            return _mm("ble,ed->bld", y.astype(dt), w_out, dt)


class GroupedQueryAttention(nn.Module):
    """Causal attention, H query heads over H_kv key/value heads, a
    per-head RMSNorm on q and k before the rotary step (none without
    ``attention_qk_norm``), scores scaled by 1 / sqrt(head_dim) or by
    ``attention_multiplier``, folded into q. ``kind`` is the
    layer's: "sliding_attention" holds a query to its ``sliding_window``
    newest keys (scope ``swa_attend``), and each kind turns by its own
    rotary table (``HybridMoEConfig.rope_for``) or, where that is
    "none", not at all. With ``attention_output_gate``,
    Op = W_o (o * sigmoid(W_g u)) (scope ``attn_gate``)."""

    cfg: Any
    kind: str = "full_attention"

    @nn.compact
    def __call__(self, u):
        from mmlspark_tpu.parallel.ring_attention import attention
        c = self.cfg
        sliding = self.kind == "sliding_attention"
        table = c.rope_for(self.kind)
        turn = {"theta": table["rope_theta"]}
        if table["rope_type"] == "yarn":
            turn["inv"], turn["factor"] = yarn_table(c.head_dim, **table)

        def turned(x):      # a kind with no table is not turned at all
            if table["rope_type"] == "none":
                return x
            return rope_rotate_half(x, pos, **turn)
        dim, dt = c.hidden_size, c.dtype
        h, hk, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        w_q = self.param("q_proj", _fan_in(dim), (dim, h, d), dt)
        w_k = self.param("k_proj", _fan_in(dim), (dim, hk, d), dt)
        w_v = self.param("v_proj", _fan_in(dim), (dim, hk, d), dt)
        w_o = self.param("out_proj", _fan_in(h * d), (h, d, dim), dt)
        if c.attention_output_gate:
            w_g = self.param("gate_proj", _fan_in(dim), (dim, h, d), dt)
        q_norm = k_norm = None
        if c.attention_qk_norm:
            q_norm = self.param("q_layernorm", _ones, (d,), dt)
            k_norm = self.param("k_layernorm", _ones, (d,), dt)

        def projected(w, gain):
            y = _mm("bld,dhk->blhk", u, w)
            return y if gain is None else rms_norm(y, gain, c.norm_eps)
        pos = jnp.arange(u.shape[1])
        with jax.named_scope("gqa_project"):
            q = projected(w_q, q_norm)
            k = projected(w_k, k_norm)
            q = turned(q)
            if c.attention_multiplier is not None:
                # the kernel's 1 / sqrt(d) times this is the multiplier
                # (exact in any dtype where the ratio is a power of two)
                q = q * (c.attention_multiplier * math.sqrt(d))
            q = q.astype(dt)
            k = turned(k).astype(dt)
            v = _mm("bld,dhk->blhk", u, w_v, dt)
        with jax.named_scope("swa_attend" if sliding else "gqa_attend"):
            o = attention(q, k, v, causal=True,
                          window=c.sliding_window if sliding else 0)
        if c.attention_output_gate:
            # the gate's projection, its sigmoid (float32) and the
            # product with the heads' outputs (``attn_gate_share``)
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(_mm("bld,dhk->blhk", u, w_g))
                o = (o.astype(_F32) * gate).astype(dt)
        with jax.named_scope("gqa_project"):
            return _mm("blhk,hkd->bld", o, w_o, dt)


class HybridMoELM(nn.Module):
    """See the module's docstring. ``cfg`` holds the sizes
    (``build_network`` makes it from the spec's keys). ``capture``:
    ``operator_<i>`` the output of layer i's operator (before the
    residual add, and before its post norm where ``sandwich_norms``),
    ``block_<i>`` the hidden state after layer i,
    ``routed_<i>`` the (b, l, k) experts an expert layer chose,
    ``final`` the normed last position. With ``num_dense_layers`` the
    depth there is no expert layer at all: no ``ExpertLayer`` is built
    and the ``moe_*`` row stats read 0."""

    int_input = True  # consumes token ids, not float features
    # per-row numbers that ride out with the logits (TPUModel observes
    # them into histograms of these names, one entry a real row)
    row_stats = ("moe_tokens_held", "moe_load_max_over_mean", "moe_passes")
    # ... and per-row arrays that ride out as outputs a ``fetchDict``
    # can name: ``routed_tail`` (b, expert layers, ROUTED_TAIL, k) int32,
    # the experts this very execution chose at each row's last positions
    # (a comparison with a reference has to know them: where score +
    # bias nearly tie, bfloat16 rightly chooses otherwise now and then),
    # and ``attention_tail`` (b, attention layers, ATTENTION_TAIL,
    # hidden), every attention operator's output at those positions,
    # and where the model has Mamba-2 layers ``ssm_tail`` (b, mamba
    # layers, ATTENTION_TAIL, hidden), every Mamba-2 operator's
    @property
    def row_outputs(self) -> Tuple[str, ...]:
        return ("routed_tail", "attention_tail") \
            + (("ssm_tail",) if self.ssm_layers else ())

    cfg: HybridMoEConfig = HybridMoEConfig()

    @property
    def moe_gather_combines(self) -> int:
        """Expert layers whose outputs return to their tokens by a
        gather (``TPUModel.metrics()`` carries it): all of them, every
        expert being here."""
        c = self.cfg
        return gather_combines(
            c, max(0, len(c.layer_types) - c.num_dense_layers))

    # ... and those layers run their down product once a layer, not once
    # a pass, and a pass's gate and up products and their silu * up as
    # one kernel that reads the pass's rows through their token ids
    # (``TPUModel.metrics()`` carries the three counts too): one branch
    # of ``routed_experts`` does all four
    moe_layer_down_products = moe_gather_combines
    moe_fused_swiglu_layers = moe_gather_combines
    moe_row_fetch_layers = moe_gather_combines

    # fetch blocks that a (row, head) of a windowed and of a causal
    # flash call visit at ``max_len`` (``TPUModel.metrics()`` carries
    # them): what the window saves is their difference a sliding layer
    flash_window_blocks = property(
        lambda self: self.cfg.flash_blocks("sliding_attention"))
    flash_causal_blocks = property(
        lambda self: self.cfg.flash_blocks("full_attention"))

    # attention operators with an output gate, attention layers that
    # take no rotary step, and shared experts an expert layer adds
    # (``TPUModel.metrics()`` carries them; static, from the spec)
    @property
    def attn_gated_layers(self) -> int:
        c = self.cfg
        return sum(k in ATTENTION for k in c.layer_types) \
            if c.attention_output_gate else 0

    @property
    def rope_free_layers(self) -> int:
        c = self.cfg
        return sum(k in ATTENTION and c.rope_for(k)["rope_type"] == "none"
                   for k in c.layer_types)

    @property
    def moe_shared_experts(self) -> int:
        c = self.cfg
        return c.num_shared_experts \
            if len(c.layer_types) > c.num_dense_layers else 0

    # Mamba-2 layers, the chunks of the scan a row of ``max_len`` takes,
    # and the bytes of a row's final scan states (float32) and conv tails
    # (the d_conv - 1 last positions of the conv's float32 input) over
    # those layers: what a decode step would carry from one token to the
    # next (``TPUModel.metrics()`` carries the three; static, from the
    # spec)
    @property
    def ssm_layers(self) -> int:
        return sum(k == "mamba" for k in self.cfg.layer_types)

    @property
    def ssm_chunks(self) -> int:
        c = self.cfg
        return -(-c.max_len // c.mamba_chunk_size) if self.ssm_layers \
            else 0

    # Mamba-2 layers whose scan runs as one Pallas kernel (ops/ssd_scan.py
    # ``kernel_runs``: on a TPU, at shapes it takes; ``TPUModel.metrics()``
    # carries it): all of them or none
    @property
    def ssm_scan_kernel_layers(self) -> int:
        from mmlspark_tpu.ops.ssd_scan import kernel_runs
        c = self.cfg
        if not self.ssm_layers or not kernel_runs(
                c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                c.mamba_n_groups, c.mamba_chunk_size):
            return 0
        return self.ssm_layers

    @property
    def ssm_state_bytes(self) -> int:
        c = self.cfg
        if not self.ssm_layers:
            return 0
        channels = c.mamba_expand * c.hidden_size \
            + 2 * c.mamba_n_groups * c.mamba_d_state
        per_layer = 4 * (c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state
                         + (c.mamba_d_conv - 1) * channels)
        return self.ssm_layers * per_layer

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 capture: Optional[str] = None):
        cfg = self.cfg
        b, l = tokens.shape
        if l > cfg.max_len:
            raise ValueError(f"sequence {l} exceeds max_len={cfg.max_len}")
        dt, dim = cfg.dtype, cfg.hidden_size
        scale = cfg.embedding_multiplier
        if scale is None and cfg.mup_enabled:
            scale = math.sqrt(dim)
        embed = self.param(
            "embed", _fan_in(dim) if scale is not None
            else nn.initializers.normal(1.0), (cfg.vocab_size, dim), dt)
        x = embed[tokens.astype(jnp.int32)]
        if scale is not None:
            x = (x.astype(_F32) * scale).astype(dt)

        def add(x, y, name):    # x + the branch's output, normed (or as
            if cfg.sandwich_norms:      # is), times residual_multiplier
                y = rms_norm(y, self.param(name, _ones, (dim,), dt),
                             cfg.norm_eps).astype(dt)
            if cfg.residual_multiplier == 1.0:
                return x + y
            return (x.astype(_F32) + cfg.residual_multiplier
                    * y.astype(_F32)).astype(dt)
        held_tokens = jnp.zeros((b,), _F32)
        imbalance, passes, expert_layers = jnp.zeros((b,), _F32), 0.0, 0
        tails, attended, mixed = [], [], []
        first = cfg.expert_rank * cfg.experts_held
        pass_rows = _pass_rows(b * l * cfg.num_experts_per_tok,
                               cfg.experts_held, cfg.num_experts)
        for i, kind in enumerate(cfg.layer_types):
            u = rms_norm(x, self.param(f"layer_{i}_operator_norm", _ones,
                                       (dim,), dt), cfg.norm_eps).astype(dt)
            if kind == "conv":
                a = ShortConv(cfg, name=f"layer_{i}_conv")(u)
            elif kind == "mamba":
                a = Mamba2Mixer(cfg, name=f"layer_{i}_mamba")(u)
                mixed.append(a[:, -ATTENTION_TAIL:])
            else:
                a = GroupedQueryAttention(cfg, kind,
                                          name=f"layer_{i}_attn")(u)
                attended.append(a[:, -ATTENTION_TAIL:])
            if capture == f"operator_{i}":
                return a
            x = add(x, a, f"layer_{i}_operator_post_norm")
            u = rms_norm(x, self.param(f"layer_{i}_ffn_norm", _ones,
                                       (dim,), dt), cfg.norm_eps).astype(dt)
            u = u.reshape(b * l, dim)
            if i < cfg.num_dense_layers:
                y = GatedMLP(cfg, cfg.intermediate_size,
                             name=f"layer_{i}_mlp")(u)
            else:
                y, chosen, load = ExpertLayer(cfg, name=f"layer_{i}_moe")(u)
                if capture == f"routed_{i}":
                    return chosen.reshape(b, l, -1)
                tails.append(chosen.reshape(b, l, -1)[:, -ROUTED_TAIL:])
                by_row = _row_loads(chosen.reshape(b, -1), first,
                                    cfg.experts_held)
                held_tokens += by_row.sum(-1)
                imbalance += by_row.max(-1) / jnp.maximum(
                    by_row.mean(-1), 1.0)
                passes += jnp.ceil(jnp.sum(load) / pass_rows)
                expert_layers += 1
            x = add(x, y.reshape(b, l, dim).astype(dt),
                    f"layer_{i}_ffn_post_norm")
            if capture == f"block_{i}":
                return x
        with jax.named_scope("lm_head_last"):
            last = rms_norm(x[:, -1], self.param(
                "embedding_norm", _ones, (dim,), dt), cfg.norm_eps).astype(dt)
            if capture == "final":
                return last
            head = embed if cfg.tie_word_embeddings else self.param(
                "lm_head", _fan_in(dim), (cfg.vocab_size, dim), dt)
            logits = _mm("bd,vd->bv", last, head)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
        self.sow("stats", "moe_tokens_held", held_tokens)
        self.sow("stats", "moe_load_max_over_mean",
                 imbalance / max(expert_layers, 1))
        self.sow("stats", "moe_passes", jnp.broadcast_to(
            jnp.asarray(passes / max(expert_layers, 1), _F32), (b,)))
        if tails:
            self.sow("stats", "routed_tail",
                     jnp.stack(tails, axis=1).astype(jnp.int32))
        if attended:
            self.sow("stats", "attention_tail", jnp.stack(attended, axis=1))
        if mixed:
            self.sow("stats", "ssm_tail", jnp.stack(mixed, axis=1))
        return logits

    def feature_layers(self) -> List[str]:
        n = len(self.cfg.layer_types)
        return ([f"block_{i}" for i in range(n)]
                + [f"operator_{i}" for i in range(n)]
                + [f"routed_{i}" for i in range(self.cfg.num_dense_layers, n)]
                + ["final"])
