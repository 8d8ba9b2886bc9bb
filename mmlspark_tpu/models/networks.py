"""Flax model zoo — the network families the reference trains/serves.

TPU-native replacement for the reference's CNTK graphs: the BrainScript
ConvNet the cntk-train notebooks build (ref: notebooks/gpu/401 BrainScript
cell; src/cntk-train/.../BrainscriptBuilder.scala:16-120), the ResNet used
for CIFAR inference (ref: notebooks 301), ImageFeaturizer backbones
(ref: src/image-featurizer), and the Bi-LSTM entity extractor
(ref: notebook 304).

All modules are standard flax.linen, NHWC layouts, bfloat16-friendly:
``dtype`` controls compute precision while params stay float32 (the
canonical TPU mixed-precision recipe — MXU eats bf16, accumulates f32).

Every module exposes ``feature_layers()`` naming its intermediate
activation points so ImageFeaturizer-style layer cutting
(ref: ImageFeaturizer.scala:91-141 cutOutputLayers/layerNames) works on
any zoo model: pass ``capture=<name>`` to ``__call__`` and the module
returns that intermediate instead of the head output.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

Dtype = Any


class MLP(nn.Module):
    """Plain MLP over flat feature vectors."""

    features: Sequence[int] = (256, 128)
    num_classes: int = 10
    dtype: Dtype = jnp.float32
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = False, capture: Optional[str] = None):
        x = x.astype(self.dtype)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, dtype=self.dtype, name=f"dense_{i}")(x)
            x = nn.relu(x)
            if self.dropout > 0:
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
            if capture == f"dense_{i}":
                return x
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)

    def feature_layers(self) -> List[str]:
        return [f"dense_{i}" for i in range(len(self.features))]


class ConvNet(nn.Module):
    """The CIFAR ConvNet family of the cntk-train notebooks: stacked
    conv-relu(-pool) blocks then dense layers (ref: notebooks/gpu/401
    BrainScript ConvNet 32:32:3)."""

    conv_features: Sequence[int] = (64, 64, 64)
    kernel: Tuple[int, int] = (3, 3)
    pool_every: int = 1
    dense_features: Sequence[int] = (256,)
    num_classes: int = 10
    dtype: Dtype = jnp.float32
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = False, capture: Optional[str] = None):
        x = x.astype(self.dtype)
        for i, f in enumerate(self.conv_features):
            x = nn.Conv(f, self.kernel, dtype=self.dtype, name=f"conv_{i}")(x)
            x = nn.relu(x)
            if (i + 1) % self.pool_every == 0:
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            if capture == f"conv_{i}":
                return x
        x = x.reshape((x.shape[0], -1))
        for i, f in enumerate(self.dense_features):
            x = nn.Dense(f, dtype=self.dtype, name=f"dense_{i}")(x)
            x = nn.relu(x)
            if self.dropout > 0:
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
            if capture == f"dense_{i}":
                return x
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)

    def feature_layers(self) -> List[str]:
        return ([f"conv_{i}" for i in range(len(self.conv_features))]
                + [f"dense_{i}" for i in range(len(self.dense_features))])


class ResNetBlock(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        # explicit symmetric (1,1) padding: identical to SAME at stride 1,
        # and matches torch's padding=1 at stride 2 (XLA SAME would pad
        # asymmetrically there), so imported torch checkpoints
        # (importers/torch_import.py) reproduce bit-comparable activations.
        # NOTE: stride-2 numerics differ from pre-torch-compat builds;
        # ResNet checkpoints saved before this change shift one pixel at
        # stage entries and should be retrained or re-imported
        pad = ((1, 1), (1, 1))
        residual = x
        y = nn.Conv(self.features, (3, 3), self.strides, padding=pad,
                    use_bias=False, dtype=self.dtype)(x)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.features, (3, 3), padding=pad, use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.features, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype,
                               name="proj")(residual)
            residual = nn.BatchNorm(use_running_average=not train,
                                    dtype=self.dtype)(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet family. ``stem='cifar'`` (default) is the CIFAR 6n+2
    style: 3x3 stem, stage_sizes=(3,3,3) -> ResNet-20.
    ``stem='imagenet'`` reproduces the torchvision ImageNet layout
    bit-for-bit (7x7/stride-2/pad-3 stem + BatchNorm + 3x3/stride-2
    maxpool with pad 1; stage_sizes=(2,2,2,2), width=64,
    num_classes=1000 -> torchvision resnet18) so published torchvision
    BasicBlock checkpoints import losslessly
    (importers/torch_import.py; ref: ModelDownloader.scala:209 — the
    reference's zoo is anchored on real published CNNs)."""

    stage_sizes: Sequence[int] = (3, 3, 3)
    width: int = 16
    num_classes: int = 10
    stem: str = "cifar"      # 'cifar' | 'imagenet'
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, capture: Optional[str] = None):
        x = x.astype(self.dtype)
        if self.stem == "imagenet":
            # torchvision: Conv2d(7, stride 2, padding 3) -> BN -> ReLU
            # -> MaxPool2d(3, stride 2, padding 1), with -inf padding so
            # the pooled border matches torch exactly
            x = nn.Conv(self.width, (7, 7), (2, 2),
                        padding=((3, 3), (3, 3)), use_bias=False,
                        dtype=self.dtype, name="stem")(x)
            x = nn.BatchNorm(use_running_average=not train,
                             dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        else:
            x = nn.Conv(self.width, (3, 3), use_bias=False,
                        dtype=self.dtype, name="stem")(x)
            x = nn.BatchNorm(use_running_average=not train,
                             dtype=self.dtype)(x)
            x = nn.relu(x)
        for s, n_blocks in enumerate(self.stage_sizes):
            for b in range(n_blocks):
                strides = (2, 2) if (s > 0 and b == 0) else (1, 1)
                x = ResNetBlock(self.width * (2 ** s), strides,
                                self.dtype, name=f"stage{s}_block{b}")(
                                    x, train=train)
            if capture == f"stage{s}":
                return x
        x = jnp.mean(x, axis=(1, 2))
        if capture == "pool":
            return x
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)

    def feature_layers(self) -> List[str]:
        return [f"stage{s}" for s in range(len(self.stage_sizes))] + ["pool"]

    def numerics_markers(self) -> Dict[str, str]:
        """Saved-stage numerics versioning (core/serialize.py hook):
        checkpoints from before the explicit-(1,1)-padding change shift
        one pixel at stride-2 stage entries — loading them must warn."""
        return {"resnet_padding": "explicit11-torch-compat"}


class BiLSTMTagger(nn.Module):
    """Bidirectional LSTM sequence tagger — the TPU twin of the notebook
    304 Bi-LSTM medical-entity extractor (Keras/CNTK backend there).

    Input: int32 token ids [B, T]; output: per-token class logits
    [B, T, num_tags]. Uses nn.RNN over LSTMCells; the backward pass uses
    ``reverse=True`` with masking-friendly fixed-length scan, which XLA
    compiles to a single fused loop on TPU.
    """

    int_input = True  # consumes token ids, not float features

    vocab_size: int = 10000
    embed_dim: int = 128
    hidden: int = 128
    num_tags: int = 8
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 capture: Optional[str] = None):
        emb = nn.Embed(self.vocab_size, self.embed_dim,
                       dtype=self.dtype, name="embed")(tokens)
        fwd = nn.RNN(nn.OptimizedLSTMCell(self.hidden), name="lstm_fwd")
        bwd = nn.RNN(nn.OptimizedLSTMCell(self.hidden), reverse=True,
                     keep_order=True, name="lstm_bwd")
        h = jnp.concatenate([fwd(emb), bwd(emb)], axis=-1)
        if capture == "lstm":
            return h
        return nn.Dense(self.num_tags, dtype=jnp.float32, name="head")(h)

    def feature_layers(self) -> List[str]:
        return ["lstm"]


class TransformerBlock(nn.Module):
    """Pre-LN decoder block; attention is pluggable so the same weights
    run dense (single chip) or ring/Ulysses (seq-sharded under
    shard_map via ``seq_axis``)."""

    dim: int
    heads: int
    mlp_ratio: int = 4
    causal: bool = True
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.parallel import ring_attention as ra
        b, l, _ = x.shape
        h = self.heads
        hd = self.dim // h
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, l, h, hd)
        k = k.reshape(b, l, h, hd)
        v = v.reshape(b, l, h, hd)
        if self.seq_axis is not None:
            fn = (ra.ring_attention if self.seq_impl == "ring"
                  else ra.ulysses_attention)
            attn = fn(q, k, v, axis_name=self.seq_axis, causal=self.causal)
        else:
            attn = ra.attention(q, k, v, causal=self.causal)
        attn = attn.reshape(b, l, self.dim)
        x = x + nn.Dense(self.dim, dtype=self.dtype, name="proj")(attn)
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        y = nn.Dense(self.mlp_ratio * self.dim, dtype=self.dtype,
                     name="mlp_up")(y)
        y = nn.gelu(y)
        x = x + nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(y)
        return x


class Transformer(nn.Module):
    """Decoder-only transformer LM / sequence classifier.

    Long-context first-class: set ``seq_axis`` and apply under shard_map
    with the sequence dimension sharded on that mesh axis — attention
    runs as ring (ppermute) or Ulysses (all_to_all) collectives and the
    positional embedding uses each shard's global offset.
    """

    int_input = True  # consumes token ids, not float features

    vocab_size: int = 32000
    dim: int = 256
    depth: int = 4
    heads: int = 8
    max_len: int = 2048
    num_classes: int = 0     # 0 -> LM head over vocab
    causal: bool = True
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    dtype: Dtype = jnp.float32
    # the vocab projection is the single largest matmul in an LM; f32
    # (default, conservative) runs it off the MXU's fast path, bf16 keeps
    # it on (losses still softmax in f32 — learner casts logits up)
    head_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 capture: Optional[str] = None):
        from jax import lax as _lax
        b, l = tokens.shape
        x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="embed")(tokens)
        pos_table = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.dim))
        if self.seq_axis is not None:
            n_shards = _lax.psum(1, self.seq_axis)  # static under shard_map
            if n_shards * l > self.max_len:
                raise ValueError(
                    f"global sequence {n_shards * l} exceeds "
                    f"max_len={self.max_len} (dynamic_slice would "
                    f"silently clamp positional embeddings)")
            offset = _lax.axis_index(self.seq_axis) * l
            pos = _lax.dynamic_slice_in_dim(pos_table, offset, l, axis=0)
        else:
            if l > self.max_len:
                raise ValueError(
                    f"sequence {l} exceeds max_len={self.max_len}")
            pos = pos_table[:l]
        x = x + pos[None].astype(self.dtype)
        for i in range(self.depth):
            x = TransformerBlock(
                self.dim, self.heads, causal=self.causal,
                seq_axis=self.seq_axis, seq_impl=self.seq_impl,
                dtype=self.dtype, name=f"block_{i}")(x)
            if capture == f"block_{i}":
                return x
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        if capture == "final":
            return x
        if self.num_classes > 0:
            # classify from the mean token representation
            pooled = jnp.mean(x, axis=1)
            if self.seq_axis is not None:
                pooled = _lax.pmean(pooled, self.seq_axis)
            return nn.Dense(self.num_classes, dtype=self.head_dtype,
                            name="head")(pooled)
        return nn.Dense(self.vocab_size, dtype=self.head_dtype,
                        name="lm_head")(x)

    def feature_layers(self) -> List[str]:
        return [f"block_{i}" for i in range(self.depth)] + ["final"]


# ---------------------------------------------------------------------------
# registry + spec construction (BrainScriptBuilder analog)
# ---------------------------------------------------------------------------

def _latent_moe_lm(**spec) -> nn.Module:
    # a file of its own: latent attention, the sparse selector and the
    # expert layers share nothing with the families above
    from mmlspark_tpu.models.latent_moe_lm import (
        LatentMoEConfig, LatentMoELM)
    return LatentMoELM(LatentMoEConfig(**spec))


def _hybrid_moe_lm(**spec) -> nn.Module:
    # a layer's operator chosen per layer (gated short convolution or
    # grouped-query attention); the expert layer is latent_moe_lm's
    from mmlspark_tpu.models.hybrid_moe_lm import (
        HybridMoEConfig, HybridMoELM)
    return HybridMoELM(HybridMoEConfig(**spec))


NETWORK_REGISTRY: Dict[str, Callable[..., nn.Module]] = {
    "mlp": MLP,
    "convnet": ConvNet,
    "resnet": ResNet,
    "bilstm": BiLSTMTagger,
    "transformer": Transformer,
    "latent_moe_lm": _latent_moe_lm,
    "hybrid_moe_lm": _hybrid_moe_lm,
}


def build_network(spec: Dict[str, Any]) -> nn.Module:
    """Build a module from a JSON-able spec — the declarative network
    definition layer replacing BrainScript emission
    (ref: BrainscriptBuilder.scala:16-120). Example::

        {"type": "resnet", "stage_sizes": [3,3,3], "num_classes": 10,
         "dtype": "bfloat16"}
    """
    spec = dict(spec)
    kind = spec.pop("type")
    if kind not in NETWORK_REGISTRY:
        raise KeyError(f"unknown network type {kind!r}; "
                       f"have {sorted(NETWORK_REGISTRY)}")
    for key in ("dtype", "head_dtype"):
        if key in spec and isinstance(spec[key], str):
            spec[key] = jnp.dtype(spec[key])
    for key in ("conv_features", "dense_features", "stage_sizes",
                "features", "kernel"):
        if key in spec and isinstance(spec[key], list):
            spec[key] = tuple(spec[key])
    return NETWORK_REGISTRY[kind](**spec)
