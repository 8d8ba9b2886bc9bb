"""The tiny ``hybrid_moe_lm`` preset of the Trinity kind that the tests
share: a dense sliding layer, a sliding and a full expert layer over a
window shorter than the row, an output gate on attention, rotary
positions on the sliding layers alone (the full one has no table), four
norms a layer, a scaled embedding, sigmoid-routed experts with a bias,
a scaling factor and a shared expert, an untied head; the plain
reference (benchmark/reference_trinity.py, which imports nothing of the
program) under the name ``reference``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_trinity as reference  # noqa: E402,F401

TINY = {
    "type": "hybrid_moe_lm", "vocab_size": 128, "max_len": 48,
    "hidden_size": 64,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 8, "num_dense_layers": 1, "intermediate_size": 96,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "norm_eps": 1e-5, "rope_theta": 10000.0,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0},
        "full_attention": {"rope_type": "none"}},
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.826, "gate_norm_eps": 1e-20,
    "scoring_func": "sigmoid", "use_expert_bias": True,
    "attention_output_gate": True, "sandwich_norms": True,
    "mup_enabled": True, "tie_word_embeddings": False}
ROWS = np.random.default_rng(0).integers(0, 128, size=(3, 48))


def build(dtype="float32", gains=True, **over):
    """(module, params) of the preset on seeded weights; with ``gains``
    every norm's gain is drawn around 1 (not left at 1), so that a gain
    at the wrong place shows."""
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
            if "norm" in jax.tree_util.keystr(path) else a, params)
    return module, params


def apply(module, params, rows, **kw):
    """The module's output for ``rows`` as a numpy array, jitted."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda p, t: module.apply(
        {"params": p}, t, **kw))(params, jnp.asarray(rows)))
