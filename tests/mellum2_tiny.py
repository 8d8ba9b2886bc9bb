"""The tiny ``hybrid_moe_lm`` preset of the Mellum2 kind that the tests
share: sliding-window layers beside a full one over a window shorter
than the row, both rotary tables (YaRN on the full layer), a head width
that is not hidden / heads, softmax-routed experts without a bias in
every layer, an untied head; the plain reference
(benchmark/reference_mellum2.py, which imports nothing of the program)
under the name ``reference``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_mellum2 as reference  # noqa: E402,F401

YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 4.0,
        "beta_slow": 1.0, "attention_factor": 1.1386294361119891}
TINY = {
    "type": "hybrid_moe_lm", "vocab_size": 128, "max_len": 48,
    "hidden_size": 64,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 8, "num_dense_layers": 0, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "norm_eps": 1e-6,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0},
        "full_attention": YARN},
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 8, "routed_scaling_factor": 1.0,
    "gate_norm_eps": 0.0, "scoring_func": "softmax",
    "use_expert_bias": False, "tie_word_embeddings": False}
ROWS = np.random.default_rng(0).integers(0, 128, size=(3, 48))


def build(dtype="float32", **over):
    """(module, params) of the preset on seeded weights."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": dtype, **TINY, **over})
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def apply(module, params, rows, **kw):
    """The module's output for ``rows`` as a numpy array, jitted."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda p, t: module.apply(
        {"params": p}, t, **kw))(params, jnp.asarray(rows)))
