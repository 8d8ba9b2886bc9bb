"""The tiny ``hybrid_moe_lm`` preset that the tests share: both operator
kinds, a dense and four expert layers, at hidden 64; the plain reference
(benchmark/reference_lfm2.py, which imports nothing of the program)
under the name ``reference``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_lfm2 as reference  # noqa: E402,F401

TINY = {
    "type": "hybrid_moe_lm", "vocab_size": 128, "max_len": 32,
    "hidden_size": 64,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 1, "num_attention_heads": 8,
    "num_key_value_heads": 2, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "conv_L_cache": 3, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
    "gate_norm_eps": 1e-6}
ROWS = np.random.default_rng(0).integers(0, 128, size=(3, 32))


def build(dtype="float32"):
    """(module, params) of the preset on seeded weights."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": dtype, **TINY})
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def apply(module, params, rows, **kw):
    """The module's output for ``rows`` as a numpy array, jitted."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda p, t: module.apply(
        {"params": p}, t, **kw))(params, jnp.asarray(rows)))
