"""``latent_moe_lm``: the decoder family of today's large open models.

A second network family beside ``networks.Transformer``: RMSNorm,
interleaved rotary positions, latent attention (low-rank query and
key/value paths, MLA), a learned sparse selector that picks each
query's keys (shared by the layers above it until the next selector),
gated (SwiGLU) feed-forwards, and expert layers with sigmoid-scored
routing, a score-correction bias, one shared expert and no dropped
token. Layer kinds come from the spec, one entry a layer:
``mlp_layer_types`` ("dense" | "sparse") and ``indexer_types``
("full" | "shared"). The field names are those of the published
``config.json`` of the ``glm_moe_dsa`` / DeepSeek-V3.2 family.

    x <- x + Attn(RMSNorm(x));  x <- x + FFN(RMSNorm(x))
    logits = W_head RMSNorm(x[last])                 (b, vocab_size)

It is a scorer: token ids (b, l) in, float32 next-token logits of the
last position out. docs/latent_moe_lm.md has the equations, the spec
keys and what ``capture`` returns.

**The chip's share.** ``experts_total`` experts exist, this chip holds
``experts_held`` of them, those of rank ``expert_rank``:
``[rank * held, (rank + 1) * held)``. The router and the normaliser of
the gates run over all of them; the layer adds its own experts' part
and the shared expert. On one chip there is no exchange, and nothing
stands in for the absent chips. ``vocab_size`` is the slice of the
vocabulary held here.

Parameters are held in ``dtype`` (bfloat16 behind the server). Matrix
products take ``dtype`` operands and accumulate in float32; norms,
rotary angles, router scores, selector scores, both top-k's and both
softmaxes are float32. Attention and the selector go one sequence at a
time and in blocks of queries, so nothing of size l x l x heads exists.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax

# the expert layer and the pieces around it are shared with
# ``hybrid_moe_lm`` and live in expert_layer.py; the names stay
# importable from here
from mmlspark_tpu.models.expert_layer import (  # noqa: F401
    PASS_SHARE, ExpertLayer, GatedMLP, _F32, _fan_in, _mm, _ones,
    _pass_rows, _row_loads, gather_combines, rms_norm, route,
    routed_experts, swiglu)
from mmlspark_tpu.ops.sparse_select import select_keys

Dtype = Any

# the LayerNorm of the selector's key (the published inference code's)
SELECTOR_KEY_NORM_EPS = 1e-6


def rope_interleaved(x, positions, theta: float):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by
    positions * theta**(-2i/d). x (l, ..., d) float32, positions (l,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """The sizes of one ``latent_moe_lm``, as ``networkSpec`` names them
    (docs/latent_moe_lm.md). The defaults are GLM-5.2's published
    widths with one chip's share of a 16-chip expert-parallel layer."""

    vocab_size: int = 19360
    max_len: int = 8192
    hidden_size: int = 6144
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 8e6
    rms_norm_eps: float = 1e-5
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = ("full",)
    mlp_layer_types: Tuple[str, ...] = ("dense",)
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    experts_total: int = 256
    experts_held: int = 16
    expert_rank: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    gate_norm_eps: float = 0.0
    dtype: Dtype = jnp.bfloat16

    def __post_init__(self):
        for name in ("indexer_types", "mlp_layer_types"):   # JSON lists
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if len(self.indexer_types) != len(self.mlp_layer_types):
            raise ValueError("indexer_types and mlp_layer_types name "
                             "one kind a layer each")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer needs a selector of its "
                             "own: indexer_types[0] must be 'full'")
        if (self.expert_rank + 1) * self.experts_held > self.experts_total:
            raise ValueError(
                f"rank {self.expert_rank} x {self.experts_held} experts "
                f"held passes experts_total={self.experts_total}")


class LatentAttention(nn.Module):
    """MLA over the selected keys, with the selector where the layer
    has one. Returns the sub-layer's output and the (b, l, l) table of
    selected keys it attended over (its own, or the one handed in)."""

    cfg: Any
    selector: bool

    @nn.compact
    def __call__(self, u, keep=None):
        c = self.cfg
        dt = c.dtype
        h, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        dim = c.hidden_size
        p = {
            "q_a": self.param("q_a", _fan_in(dim), (dim, c.q_lora_rank), dt),
            "q_a_norm": self.param("q_a_norm", _ones, (c.q_lora_rank,), dt),
            "q_b": self.param("q_b", _fan_in(c.q_lora_rank),
                              (c.q_lora_rank, h, dn + dr), dt),
            "kv_a": self.param("kv_a", _fan_in(dim),
                               (dim, c.kv_lora_rank + dr), dt),
            "kv_a_norm": self.param("kv_a_norm", _ones,
                                    (c.kv_lora_rank,), dt),
            "kv_b": self.param("kv_b", _fan_in(c.kv_lora_rank),
                               (c.kv_lora_rank, h, dn + dv), dt),
            "o": self.param("o", _fan_in(h * dv), (h, dv, dim), dt),
        }
        if self.selector:
            j, di = c.index_n_heads, c.index_head_dim
            p.update(
                idx_q=self.param("idx_q", _fan_in(c.q_lora_rank),
                                 (c.q_lora_rank, j, di), dt),
                idx_k=self.param("idx_k", _fan_in(dim), (dim, di), dt),
                idx_k_norm_scale=self.param("idx_k_norm_scale", _ones,
                                            (di,), dt),
                idx_k_norm_bias=self.param(
                    "idx_k_norm_bias", nn.initializers.zeros, (di,), dt),
                idx_w=self.param("idx_w", _fan_in(dim), (dim, j), dt))
        if not self.selector and keep is None:
            raise ValueError("a layer without a selector attends over "
                             "the set of a layer below it")

        def one(args):
            u_row, keep_row = args
            return _attend_row(p, c, u_row, keep_row)
        xs = (u, keep if not self.selector else jnp.zeros(
            (u.shape[0], 0), jnp.bool_))
        return lax.map(one, xs)


def _attend_row(p, c, u, keep):
    """One sequence: u (l, dim) normed input -> (out (l, dim), keep)."""
    from mmlspark_tpu.parallel.ring_attention import selected_attention
    dt = u.dtype
    length = u.shape[0]
    pos = jnp.arange(length)
    dn, dr = c.qk_nope_head_dim, c.qk_rope_head_dim
    with jax.named_scope("mla_project"):
        c_q = rms_norm(_mm("ld,dr->lr", u, p["q_a"]), p["q_a_norm"],
                       c.rms_norm_eps).astype(dt)
        q = _mm("lr,rhk->lhk", c_q, p["q_b"])
        kv = _mm("ld,dr->lr", u, p["kv_a"])
        c_kv = rms_norm(kv[:, :c.kv_lora_rank], p["kv_a_norm"],
                        c.rms_norm_eps).astype(dt)
        k_rope = rope_interleaved(kv[:, c.kv_lora_rank:], pos, c.rope_theta)
        kv_up = _mm("lr,rhk->hlk", c_kv, p["kv_b"], dt)
        q_rope = rope_interleaved(q[..., dn:], pos, c.rope_theta)
        scale = (dn + dr) ** -0.5
        q = (jnp.concatenate([q[..., :dn], q_rope], -1) * scale
             ).astype(dt).transpose(1, 0, 2)
        k = jnp.concatenate([
            kv_up[..., :dn],
            jnp.broadcast_to(k_rope.astype(dt)[None],
                             (kv_up.shape[0], length, dr))], -1)
        v = kv_up[..., dn:]
    if "idx_q" in p:
        keep = _select_row(p, c, u, c_q, pos)
    with jax.named_scope("dsa_attend"):
        o = selected_attention(q[None], k[None], v[None], keep[None])[0]
    with jax.named_scope("mla_project"):
        return _mm("hlv,hvd->ld", o, p["o"], dt), keep


def _select_row(p, c, u, c_q, pos):
    """The selector's (l, l) table of one sequence (``select_keys``
    makes no score for a query that keeps every key)."""
    di, j = c.index_head_dim, c.index_n_heads

    def rope_head(x):       # rotary on the first qk_rope_head_dim dims
        dr = c.qk_rope_head_dim
        return jnp.concatenate([
            rope_interleaved(x[..., :dr], pos, c.rope_theta),
            x[..., dr:]], -1).astype(u.dtype)
    with jax.named_scope("dsa_score"):
        q_i = _mm("lr,rjd->ljd", c_q, p["idx_q"])
        k_i = _mm("ld,de->le", u, p["idx_k"])
        mean = k_i.mean(-1, keepdims=True)
        var = jnp.mean((k_i - mean) ** 2, -1, keepdims=True)
        k_i = ((k_i - mean) * lax.rsqrt(var + SELECTOR_KEY_NORM_EPS)
               * p["idx_k_norm_scale"].astype(_F32)
               + p["idx_k_norm_bias"].astype(_F32))
        q_i, k_i = rope_head(q_i), rope_head(k_i)
        w = _mm("ld,dj->lj", u, p["idx_w"]) * (j ** -0.5 * di ** -0.5)
    return select_keys(q_i, w, k_i, c.index_topk)


class LatentMoELM(nn.Module):
    """See the module's docstring. ``cfg`` holds the sizes
    (``build_network`` makes it from the spec's keys). ``capture``:
    ``block_<i>`` the hidden state after layer i, ``selected_<i>`` the
    (b, l, l) bool table of keys layer i attended over, ``routed_<i>``
    the (b, l, k) experts an expert layer chose, ``final`` the normed
    last position."""

    int_input = True  # consumes token ids, not float features
    # per-row numbers that ride out with the logits (TPUModel observes
    # them into histograms of these names, one entry a real row)
    row_stats = ("moe_tokens_held", "moe_load_max_over_mean",
                 "dsa_keys_per_query")

    cfg: LatentMoEConfig = LatentMoEConfig()

    @property
    def moe_gather_combines(self) -> int:
        """Expert layers whose outputs return to their tokens by a
        gather (``TPUModel.metrics()`` carries it): none where a share
        of the experts is held, all of them where every one is."""
        c = self.cfg
        return gather_combines(
            c, sum(kind == "sparse" for kind in c.mlp_layer_types))

    # ... and those layers run their down product once a layer, not once
    # a pass, and a pass's gate and up products and their silu * up as
    # one kernel that reads the pass's rows through their token ids
    # (``TPUModel.metrics()`` carries the three counts too): one branch
    # of ``routed_experts`` does all four
    moe_layer_down_products = moe_gather_combines
    moe_fused_swiglu_layers = moe_gather_combines
    moe_row_fetch_layers = moe_gather_combines

    # shared experts an expert layer adds beside its routed ones
    # (``TPUModel.metrics()`` carries it, as for ``hybrid_moe_lm``)
    moe_shared_experts = property(
        lambda self: self.cfg.n_shared_experts
        if "sparse" in self.cfg.mlp_layer_types else 0)

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 capture: Optional[str] = None):
        cfg = self.cfg
        b, l = tokens.shape
        if l > cfg.max_len:
            raise ValueError(f"sequence {l} exceeds max_len={cfg.max_len}")
        dt, dim = cfg.dtype, cfg.hidden_size
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (cfg.vocab_size, dim), dt)
        x = embed[tokens.astype(jnp.int32)]
        keep = None
        held_tokens = jnp.zeros((b,), _F32)
        imbalance, expert_layers = jnp.zeros((b,), _F32), 0
        for i, (mlp, idx) in enumerate(zip(cfg.mlp_layer_types,
                                           cfg.indexer_types)):
            u = rms_norm(x, self.param(f"layer_{i}_attn_norm", _ones,
                                       (dim,), dt),
                         cfg.rms_norm_eps).astype(dt)
            a, keep = LatentAttention(cfg, idx == "full",
                                      name=f"layer_{i}_attn")(u, keep)
            if capture == f"selected_{i}":
                return keep
            x = x + a
            u = rms_norm(x, self.param(f"layer_{i}_ffn_norm", _ones,
                                       (dim,), dt),
                         cfg.rms_norm_eps).astype(dt)
            u = u.reshape(b * l, dim)
            if mlp == "dense":
                y = GatedMLP(cfg, cfg.intermediate_size,
                             name=f"layer_{i}_mlp")(u)
            else:
                y, chosen, _ = ExpertLayer(cfg, name=f"layer_{i}_moe")(u)
                if capture == f"routed_{i}":
                    return chosen.reshape(b, l, -1)
                load = _row_loads(chosen.reshape(b, -1), cfg.expert_rank
                                  * cfg.experts_held, cfg.experts_held)
                held_tokens += load.sum(-1)
                imbalance += load.max(-1) / jnp.maximum(load.mean(-1), 1.0)
                expert_layers += 1
            x = x + y.reshape(b, l, dim).astype(dt)
            if capture == f"block_{i}":
                return x
        with jax.named_scope("lm_head_last"):
            last = rms_norm(x[:, -1], self.param(
                "final_norm", _ones, (dim,), dt),
                cfg.rms_norm_eps).astype(dt)
            if capture == "final":
                return last
            head = self.param("lm_head", _fan_in(dim),
                              (dim, cfg.vocab_size), dt)
            logits = _mm("bd,dv->bv", last, head)
        self.sow("stats", "moe_tokens_held", held_tokens)
        self.sow("stats", "moe_load_max_over_mean",
                 imbalance / max(expert_layers, 1))
        self.sow("stats", "dsa_keys_per_query",
                 jnp.mean(jnp.sum(keep.astype(_F32), -1), -1))
        return logits

    def feature_layers(self) -> List[str]:
        kinds = self.cfg.mlp_layer_types
        return ([f"block_{i}" for i in range(len(kinds))]
                + [f"selected_{i}" for i in range(len(kinds))]
                + [f"routed_{i}" for i, kind in enumerate(kinds)
                   if kind == "sparse"]
                + ["final"])
