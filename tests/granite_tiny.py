"""The tiny ``hybrid_moe_lm`` preset of the Granite kind that the tests
share: two Mamba-2 layers, an attention layer with no positions and no
q/k norms, one more Mamba-2 layer, a dense feed-forward in every layer
and no expert at all, the µP multipliers, a tied head; a state of 16 at
chunks of 8 over rows of 37 tokens, so that the last chunk is padded;
the plain reference (benchmark/reference_granite.py, which imports
nothing of the program) under the name ``reference``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_granite as reference  # noqa: E402,F401

TINY = {
    "type": "hybrid_moe_lm", "vocab_size": 128, "max_len": 37,
    "hidden_size": 64,
    "layer_types": ["mamba", "mamba", "full_attention", "mamba"],
    "num_dense_layers": 4, "intermediate_size": 96,
    "num_experts": 0, "num_experts_per_tok": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "norm_eps": 1e-5, "rope_theta": 10000.0,
    "rope_parameters": {"full_attention": {"rope_type": "none"}},
    "attention_qk_norm": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "logits_scaling": 8.0, "tie_word_embeddings": True,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False}
ROWS = np.random.default_rng(0).integers(0, 128, size=(3, 37))


def build(dtype="float32", gains=True, **over):
    """(module, params) of the preset on seeded weights; with ``gains``
    every norm's gain and D are drawn around 1 (not left at 1), so that
    one at the wrong place shows."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": dtype, **TINY, **over})
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    if gains:
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a * (1 + 0.2 * jax.random.normal(
                next(keys), a.shape, a.dtype))
            if "norm" in jax.tree_util.keystr(path)
            or jax.tree_util.keystr(path).endswith("['D']") else a, params)
    return module, params


def apply(module, params, rows, **kw):
    """The module's output for ``rows`` as a numpy array, jitted."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda p, t: module.apply(
        {"params": p}, t, **kw))(params, jnp.asarray(rows)))
