"""``hybrid_moe_lm``: a decoder whose layers choose their *operator*.

A third network family beside ``networks.Transformer`` and
``latent_moe_lm``: each layer's sequence operator comes from
``layer_types``, one entry a layer, and one kind is not attention at
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
before the output projection. The first
``num_dense_layers`` layers have a gated (SwiGLU) feed-forward, the
others the expert layer of expert_layer.py (sigmoid scores and a bias
that enters the choice only, or by ``scoring_func`` and
``use_expert_bias`` a softmax over every expert and no bias;
``num_shared_experts`` shared experts beside the routed ones), with
every expert on this chip. The field names are those of the
published ``config.json`` of the ``lfm2_moe`` family (LFM2-24B-A2B), of
the ``mellum`` family (Mellum2-12B-A2.5B) and of the ``afmoe`` family
(Trinity-Mini).

    h = x + Op_i(RMSNorm(x));  x' = h + FFN_i(RMSNorm(h))
    with ``sandwich_norms``: h = x + RMSNorm(Op_i(RMSNorm(x))), and the
        same around FFN_i: four gains a layer
    x_0 = embed[tokens], times sqrt(hidden_size) with ``mup_enabled``
    logits = W RMSNorm(x[last])     W the tied (vocab, hidden) embedding,
                                    or ``lm_head`` where it is not tied

It is a scorer: token ids (b, l) in, float32 next-token logits of the
last position out. docs/hybrid_moe_lm.md has the equations, the spec
keys, what ``capture`` returns, the scopes and the counters.

Parameters are held in ``dtype`` (bfloat16 behind the server). Matrix
products take ``dtype`` operands and accumulate in float32; norms,
rotary angles, the convolution's gates and taps, router scores, top-k
and softmax are float32.
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
OPERATORS = ("conv", "full_attention", "sliding_attention")
ATTENTION = OPERATORS[1:]
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


class GroupedQueryAttention(nn.Module):
    """Causal attention, H query heads over H_kv key/value heads, a
    per-head RMSNorm on q and k before the rotary step. ``kind`` is the
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
        q_norm = self.param("q_layernorm", _ones, (d,), dt)
        k_norm = self.param("k_layernorm", _ones, (d,), dt)
        pos = jnp.arange(u.shape[1])
        with jax.named_scope("gqa_project"):
            q = rms_norm(_mm("bld,dhk->blhk", u, w_q), q_norm, c.norm_eps)
            k = rms_norm(_mm("bld,dhk->blhk", u, w_k), k_norm, c.norm_eps)
            q = turned(q).astype(dt)
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
    ``final`` the normed last position."""

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
    # hidden), every attention operator's output at those positions
    row_outputs = ("routed_tail", "attention_tail")

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

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 capture: Optional[str] = None):
        cfg = self.cfg
        b, l = tokens.shape
        if l > cfg.max_len:
            raise ValueError(f"sequence {l} exceeds max_len={cfg.max_len}")
        dt, dim = cfg.dtype, cfg.hidden_size
        embed = self.param(
            "embed", _fan_in(dim) if cfg.mup_enabled
            else nn.initializers.normal(1.0), (cfg.vocab_size, dim), dt)
        x = embed[tokens.astype(jnp.int32)]
        if cfg.mup_enabled:
            x = (x.astype(_F32) * math.sqrt(dim)).astype(dt)

        def post(y, name):      # the branch's output, normed (or as is)
            if not cfg.sandwich_norms:
                return y
            return rms_norm(y, self.param(name, _ones, (dim,), dt),
                            cfg.norm_eps).astype(dt)
        held_tokens = jnp.zeros((b,), _F32)
        imbalance, passes, expert_layers = jnp.zeros((b,), _F32), 0.0, 0
        tails, attended = [], []
        first = cfg.expert_rank * cfg.experts_held
        pass_rows = _pass_rows(b * l * cfg.num_experts_per_tok,
                               cfg.experts_held, cfg.num_experts)
        for i, kind in enumerate(cfg.layer_types):
            u = rms_norm(x, self.param(f"layer_{i}_operator_norm", _ones,
                                       (dim,), dt), cfg.norm_eps).astype(dt)
            if kind == "conv":
                a = ShortConv(cfg, name=f"layer_{i}_conv")(u)
            else:
                a = GroupedQueryAttention(cfg, kind,
                                          name=f"layer_{i}_attn")(u)
                attended.append(a[:, -ATTENTION_TAIL:])
            if capture == f"operator_{i}":
                return a
            x = x + post(a, f"layer_{i}_operator_post_norm")
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
            x = x + post(y.reshape(b, l, dim).astype(dt),
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
        return logits

    def feature_layers(self) -> List[str]:
        n = len(self.cfg.layer_types)
        return ([f"block_{i}" for i in range(n)]
                + [f"operator_{i}" for i in range(n)]
                + [f"routed_{i}" for i in range(self.cfg.num_dense_layers, n)]
                + ["final"])
