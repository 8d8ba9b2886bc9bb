"""The tiny ``latent_moe_lm`` preset that the tests share: every kind of
layer the ``glm-5.2-ep16`` configuration has, at hidden 64; the plain
reference (benchmark/reference_glm_dsa.py, which imports nothing of the
program) under the name ``reference``."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_glm_dsa as reference  # noqa: E402,F401

TINY = {
    "type": "latent_moe_lm", "vocab_size": 128, "max_len": 32,
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
    "indexer_types": ["full", "shared", "shared", "shared", "full"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "experts_total": 16, "experts_held": 4, "expert_rank": 1,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5}
ROWS = np.random.default_rng(0).integers(0, 128, size=(3, 32))


def build(dtype="float32"):
    """(module, params) of the preset on seeded weights."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": dtype, **TINY})
    # the parameters do not depend on the row's length, and a row no
    # longer than index_topk compiles no selection
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def tiny_with_reference():
    """(module, params, what the reference gives for ROWS)."""
    module, params = build()
    return module, params, reference.forward(params, ROWS, TINY)


def apply(module, params, rows, **kw):
    """The module's output for ``rows`` as a numpy array, jitted."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda p, t: module.apply(
        {"params": p}, t, **kw))(params, jnp.asarray(rows)))
