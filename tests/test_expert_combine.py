"""How the routed experts' outputs return to their tokens
(``expert_layer.routed_experts``). Where every expert is held the pairs
are a permutation and the combine is a gather and a sum of k: against a
plain per-token loop in float32 numpy, in several sizes, with one pass
and with several, with a last pass that is not full, with a padded row
and with every pair on one expert; no scatter-add in its jaxpr. Where a
share is held the scatter-add stays, bit for bit the form it had before
(kept here as the plain form), and GLM's expert layer traces to the
jaxpr it had before. Where every expert is held the down product runs
once a layer, after the passes, and nothing reads a row of no group.
On a TPU a pass of that branch is one grouped call (gate, up and
silu * up in one kernel, no float32 rows outside it), and the branch
with the kernel interpreted in its loop equals the per-token loop. The
number of expert layers that combine by a gather, that run their down
product once, and whose passes are that one kernel, through
``TPUModel.metrics()``."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mmlspark_tpu.models import expert_layer as el

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import hybrid_moe_tiny  # noqa: E402
import latent_moe_tiny  # noqa: E402

# (tokens, k, experts, dim, width, PASS_ROWS_MAX or None, passes)
SIZES = {
    "one_pass": (64, 4, 8, 16, 8, None, 1),
    "four_passes": (512, 4, 8, 16, 8, 512, 4),
    "last_pass_not_full": (300, 4, 16, 32, 8, 512, 3),
    "two_a_token": (520, 2, 4, 8, 16, 512, 3),
}
ROUTINGS = ("random", "padded_row", "one_expert")


def _inputs(name, routing, seed=0):
    t, k, experts, dim, width, _, _ = SIZES[name]
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    u, gates = f32(t, dim), rng.random((t, k)).astype(np.float32)
    if routing == "random":
        # k distinct experts a token, as top-k gives
        chosen = np.stack([rng.permutation(experts)[:k] for _ in range(t)])
    elif routing == "padded_row":
        # one token repeated: every pair to the same k experts
        u = np.repeat(u[:1], t, axis=0)
        gates = np.repeat(gates[:1], t, axis=0)
        chosen = np.repeat(rng.permutation(experts)[None, :k], t, axis=0)
    else:
        chosen = np.full((t, k), experts - 1)
    return (u, chosen.astype(np.int32), gates, f32(experts, dim, width),
            f32(experts, dim, width), f32(experts, width, dim))


def _per_token_loop(u, chosen, gates, w_gate, w_up, w_down):
    """sum_j g_j E_j(u) a token, slot by slot, in float32 numpy."""
    y = np.zeros_like(u)
    for t in range(len(u)):
        for j in range(chosen.shape[1]):
            e = chosen[t, j]
            a = u[t] @ w_gate[e]
            h = (a / (1.0 + np.exp(-a))) * (u[t] @ w_up[e])
            y[t] += gates[t, j] * (h @ w_down[e])
    return y


def _cap(monkeypatch, name):
    t, k, experts, _, _, cap, passes = SIZES[name]
    if cap is not None:
        monkeypatch.setattr(el, "PASS_ROWS_MAX", cap)
    assert -(-t * k // el._pass_rows(t * k, experts, experts)) == passes


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("name", SIZES)
def test_every_expert_held_equals_a_per_token_loop(monkeypatch, name,
                                                   routing):
    _cap(monkeypatch, name)
    args = _inputs(name, routing)
    experts = args[3].shape[0]
    y, load = jax.jit(lambda *a: el.routed_experts(*a, 0, experts))(*args)
    assert y.dtype == jnp.float32
    want = _per_token_loop(*args)
    assert np.linalg.norm(np.asarray(y) - want) \
        < 2e-6 * np.linalg.norm(want)
    assert load.tolist() == np.bincount(args[1].reshape(-1),
                                        minlength=experts).tolist()


def _primitives(jaxpr, found=None):
    """Every primitive's name in a jaxpr, the nested ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("name", SIZES)
def test_every_expert_held_has_no_scatter_add(monkeypatch, name):
    _cap(monkeypatch, name)
    args = _inputs(name, "random")
    experts = args[3].shape[0]
    prims = _primitives(jax.make_jaxpr(
        lambda *a: el.routed_experts(*a, 0, experts))(*args).jaxpr)
    assert "scatter-add" not in prims and "scatter_add" not in prims
    assert "gather" in prims


def _outputs(jaxpr, primitive, found=None):
    """(shape, dtype) of every output of ``primitive`` in a jaxpr, the
    nested ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found += [(v.aval.shape, v.aval.dtype) for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _outputs(sub, primitive, found)
    return found


@pytest.mark.parametrize("name", SIZES)
def test_the_down_product_runs_once_a_layer(monkeypatch, name):
    """Gate and up in the passes' loop, down once after it, and its
    output is the layer's buffer: no (pairs, hidden) float32 array is
    filled first, the loop carries (passes x rows, width) rows in the
    input's dtype, and that buffer is not filled either (the passes
    write all of it)."""
    _cap(monkeypatch, name)
    t, k, experts, dim, width, _, passes = SIZES[name]
    u, *rest = _inputs(name, "random")
    args = (u.astype(jnp.bfloat16), *rest)
    jaxpr = jax.make_jaxpr(
        lambda *a: el.routed_experts(*a, 0, experts))(*args).jaxpr
    loop, = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert _primitives(loop.params["body_jaxpr"].jaxpr).count(
        "ragged_dot_general") == 2
    assert _primitives(jaxpr).count("ragged_dot_general") == 3
    rows = passes * el._pass_rows(t * k, experts, experts)
    assert _outputs(jaxpr, "empty") == [((rows, width), jnp.bfloat16)]
    assert not [shape for shape, _ in _outputs(jaxpr, "broadcast_in_dim")
                if len(shape) == 2 and shape[0] >= t * k and shape[1] > 1]
    # no row is selected against its group anywhere (the zeroing pass)
    assert not [shape for shape, _ in _outputs(jaxpr, "select_n")
                if len(shape) == 2]


def test_on_the_chip_a_pass_is_one_grouped_call(monkeypatch):
    """As the chip traces the branch: one Pallas call a pass (gate, up
    and silu * up: ``grouped_swiglu``) and one after the loop (the down
    product), no ``ragged_dot``, and outside the kernels no float32
    (rows, width) value: the gate and up rows never reach HBM."""
    from test_grouped_swiglu import values_outside_kernels
    name = "four_passes"
    _cap(monkeypatch, name)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, k, experts, dim, width, _, passes = SIZES[name]
    u, *rest = _inputs(name, "random")
    jaxpr = jax.make_jaxpr(lambda *a: el.routed_experts(*a, 0, experts))(
        u.astype(jnp.bfloat16), *rest).jaxpr
    loop, = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert _primitives(loop.params["body_jaxpr"].jaxpr).count(
        "pallas_call") == 1
    prims = _primitives(jaxpr)
    assert prims.count("pallas_call") == 2
    assert "ragged_dot_general" not in prims
    rows = el._pass_rows(t * k, experts, experts)
    values = values_outside_kernels(jaxpr)
    assert ((passes * rows, width), jnp.bfloat16) in values
    assert not [shape for shape, dtype in values
                if dtype == jnp.float32 and shape in (
                    (rows, width), (passes * rows, width))]


def test_a_module_builds_the_kernel_once_for_all_its_layers(monkeypatch):
    """Eight expert layers of one shape, as the chip traces them: the
    kernel's body is traced and built once (``_grouped_swiglu`` is one
    jitted function of its shapes), not once a layer, and the step's
    program names it once."""
    from jax.experimental import pallas
    from mmlspark_tpu.models.networks import build_network
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    built = []
    real = pallas.pallas_call

    def counting(*a, **kw):
        built.append(kw.get("name"))
        return real(*a, **kw)
    monkeypatch.setattr(pallas, "pallas_call", counting)
    module = build_network({"dtype": "bfloat16", **NINE_LAYERS,
                            "max_len": 256})
    assert module.moe_row_fetch_layers == 8
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    built.clear()
    text = str(jax.make_jaxpr(
        lambda v, t: module.apply(v, t, mutable=["stats"]))(
        variables, jax.ShapeDtypeStruct((2, 256), jnp.int32)))
    assert built.count("grouped_swiglu") == 1
    assert text.count("name=grouped_swiglu") == 1


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("name", SIZES)
def test_the_kernel_in_the_passes_equals_a_per_token_loop(monkeypatch, name,
                                                          routing):
    """The whole branch with the Pallas kernel, interpreted, in its
    loop (the buffer aliased to the kernel's output, a slice a pass, a
    last pass not full among the sizes; a row tile that divides these
    small passes) against the per-token loop."""
    from mmlspark_tpu.ops.grouped_matmul import _grouped_swiglu, _table
    _cap(monkeypatch, name)
    # the passes read their rows through ids from the table the chip
    # reads them from
    monkeypatch.setattr(el, "row_table", lambda u, rows: _table(u))
    monkeypatch.setattr(
        el, "grouped_swiglu",
        lambda table, w_gate, w_up, sizes, into, lo, tok: _grouped_swiglu(
            table, tok, w_gate, w_up, sizes.astype(jnp.int32), into, lo,
            tiles=(min(128, tok.shape[0]), w_gate.shape[2]),
            interpret=True))
    args = _inputs(name, routing)
    experts = args[3].shape[0]
    y, _ = jax.jit(lambda *a: el.routed_experts(*a, 0, experts))(*args)
    want = _per_token_loop(*args)
    assert np.isfinite(np.asarray(y)).all()
    assert np.linalg.norm(np.asarray(y) - want) \
        < 2e-6 * np.linalg.norm(want)


def _nan_where_no_group(real):
    def patched(lhs, rhs, group_sizes, out_dtype=jnp.bfloat16, **kw):
        out = real(lhs, rhs, group_sizes, out_dtype, **kw)
        in_group = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(in_group[:, None], out, jnp.nan)
    return patched


def _nan_past_the_pairs(real):
    def patched(table, w_gate, w_up, group_sizes, into, lo, tok):
        out = real(table, w_gate, w_up, group_sizes, into, lo, tok=tok)
        m = tok.shape[0]
        rows = lax.dynamic_slice_in_dim(out, lo, m)
        in_group = jnp.arange(m) < jnp.sum(group_sizes)
        return lax.dynamic_update_slice_in_dim(
            out, jnp.where(in_group[:, None], rows, jnp.nan), lo, 0)
    return patched


@pytest.mark.parametrize("routing", ("random", "one_expert"))
@pytest.mark.parametrize("name", SIZES)
def test_nothing_reads_a_row_of_no_group(monkeypatch, name, routing):
    """What ``rest_unread`` promises ``grouped_matmul``, and what
    ``grouped_swiglu`` leaves in a pass's rows past the pairs: with NaN
    in every row of no group (a last pass not full has them in all
    three products) the result is finite and the same to the bit."""
    _cap(monkeypatch, name)
    args = _inputs(name, routing)
    experts = args[3].shape[0]
    fn = lambda *a: el.routed_experts(*a, 0, experts)[0]  # noqa: E731
    want = np.asarray(jax.jit(fn)(*args))
    monkeypatch.setattr(el, "grouped_matmul",
                        _nan_where_no_group(el.grouped_matmul))
    monkeypatch.setattr(el, "grouped_swiglu",
                        _nan_past_the_pairs(el.grouped_swiglu))
    got = np.asarray(jax.jit(fn)(*args))
    assert np.isfinite(got).all()
    assert np.array_equal(got, want)


def test_rest_unread_skips_the_zeroing_pass_and_nothing_else():
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
    rng = np.random.default_rng(0)
    lhs = rng.standard_normal((64, 16)).astype(np.float32)
    rhs = rng.standard_normal((4, 16, 8)).astype(np.float32)
    sizes = jnp.asarray([10, 0, 30, 8], jnp.int32)
    kept = grouped_matmul(lhs, rhs, sizes, jnp.float32)
    raw = grouped_matmul(lhs, rhs, sizes, jnp.float32, rest_unread=True)
    assert np.array_equal(np.asarray(kept[:48]), np.asarray(raw[:48]))
    assert not np.asarray(kept[48:]).any()
    with pytest.raises(TypeError):      # keyword-only: no caller by accident
        grouped_matmul(lhs, rhs, sizes, jnp.float32, True)
    sel = lambda **kw: _primitives(jax.make_jaxpr(  # noqa: E731
        lambda a, b, s: grouped_matmul(a, b, s, jnp.float32, **kw))(
        lhs, rhs, sizes).jaxpr).count("select_n")
    assert sel() == sel(rest_unread=True) + 1


def _scatter_add_form(u, chosen, gates, w_gate, w_up, w_down, first, total):
    """``routed_experts`` as it was for every case before the gather
    combine, and is for a share of the experts: the plain form."""
    from mmlspark_tpu.ops.grouped_matmul import grouped_matmul
    f32 = jnp.float32
    t, k = chosen.shape
    held = w_gate.shape[0]
    local = (chosen - first).reshape(-1)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum((key[:, None] == jnp.arange(held)[None, :]
                    ).astype(jnp.int32), axis=0)
    ends = jnp.cumsum(load)
    n_here = ends[-1]
    rows = el._pass_rows(t * k, held, total)
    pad = -(-t * k // rows) * rows - t * k
    token_of = jnp.pad(order // k, (0, pad))
    gate_of = jnp.pad(gates.reshape(-1)[order], (0, pad))

    def one_pass(i, y):
        lo = i * rows
        tok = lax.dynamic_slice_in_dim(token_of, lo, rows)
        gate = lax.dynamic_slice_in_dim(gate_of, lo, rows)
        sizes = jnp.clip(ends - lo, 0, rows) \
            - jnp.clip(ends - load - lo, 0, rows)
        x = u[tok]
        h = jax.nn.silu(grouped_matmul(x, w_gate, sizes, f32)) \
            * grouped_matmul(x, w_up, sizes, f32)
        out = grouped_matmul(h.astype(u.dtype), w_down, sizes, f32)
        live = (lo + jnp.arange(rows)) < n_here
        out = jnp.where(live[:, None], out * gate[:, None], 0.0)
        return y.at[tok].add(out)

    y = lax.fori_loop(0, (n_here + rows - 1) // rows, one_pass,
                      jnp.zeros((t, u.shape[1]), f32))
    return y, load


# (held, total, first, routing): a share that sees a few pairs, a share
# that every pair falls to (four passes of 80), the last share
SHARES = [(4, 16, 4, "random"), (4, 16, 8, "all_here"),
          (2, 8, 6, "random"), (8, 16, 0, "random")]


@pytest.mark.parametrize("held,total,first,routing", SHARES)
def test_a_share_of_the_experts_keeps_its_scatter_add(held, total, first,
                                                      routing):
    t, k, dim, width = 64, 4, 16, 8
    rng = np.random.default_rng(held + first)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    if routing == "all_here":
        chosen = np.tile(first + np.arange(k)[None], (t, 1))
    else:
        chosen = np.stack([rng.permutation(total)[:k] for _ in range(t)])
    args = (f32(t, dim), chosen.astype(np.int32),
            rng.random((t, k)).astype(np.float32), f32(held, dim, width),
            f32(held, dim, width), f32(held, width, dim))
    assert not el.combines_by_gather(held, total)
    new = lambda *a: el.routed_experts(*a, first, total)  # noqa: E731
    old = lambda *a: _scatter_add_form(*a, first, total)  # noqa: E731
    y, load = jax.jit(new)(*args)
    want, want_load = jax.jit(old)(*args)
    assert np.array_equal(np.asarray(y), np.asarray(want))    # bit for bit
    assert load.tolist() == want_load.tolist()
    prims = _primitives(jax.make_jaxpr(new)(*args).jaxpr)
    assert prims.count("scatter-add") == 1
    assert prims == _primitives(jax.make_jaxpr(old)(*args).jaxpr)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(config):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        return json.load(f)["networkSpec"]


# sha256 of str(jax.make_jaxpr(ExpertLayer(cfg).apply)) for the share
# branch, taken at commit 375de4a (the parent of the PR that moved the
# down product out of the passes where every expert is held) with this
# test's own code: the tiny preset; ``glm-5.2-ep16`` at its cell's 4 x
# 8192 tokens off the chip (``ragged_dot``) and as the chip traces it
# (the megablox kernel), traced and never run
_PARENT = {
    "tiny": (latent_moe_tiny.TINY, 96, "float32", "cpu",
             "f1bd425d9906bd45c355eb41f80af316"
             "c8523a9ab88aaa7adc5525d471bf7cd4"),
    "cell": ("glm-5.2-ep16", 4 * 8192, "bfloat16", "cpu",
             "3ef63ec1f00574c732feb097b024a0ab"
             "e2516c9b398d208bfd434c8e5465ee2a"),
    "cell_on_the_chip": ("glm-5.2-ep16", 4 * 8192, "bfloat16", "tpu",
                         "4a6febdfec18138fd0636bbe21312ff5"
                         "5de8df884e342beb5423b14df1a1d2f5"),
}


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_a_share_of_the_experts_traces_to_the_parent_s_jaxpr(monkeypatch,
                                                             case):
    from mmlspark_tpu.models.networks import build_network
    spec, tokens, dtype, backend, digest = _PARENT[case]
    if isinstance(spec, str):
        spec = _spec(spec)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = build_network({"dtype": dtype, **spec}).cfg
    assert not el.combines_by_gather(cfg.experts_held, cfg.experts_total)
    layer = el.ExpertLayer(cfg)
    u = jax.ShapeDtypeStruct((tokens, cfg.hidden_size), cfg.dtype)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)
    text = str(jax.make_jaxpr(layer.apply)(params, u))
    assert "expert_layer.py" not in text        # no line numbers in it
    assert text.count("scatter-add") >= 1
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_rule_between_the_two_is_a_fact_of_the_shapes():
    assert el.combines_by_gather(64, 64)
    assert not el.combines_by_gather(16, 256)
    assert not el.combines_by_gather(63, 64)


# ---------------------------------------------------- the engagement number

def _served(spec):
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.models.tpu_model import TPUModel
    module = build_network({"dtype": "float32", **spec})
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return TPUModel.from_flax(module, {"params": params},
                              inputCol="features", outputCol="scores",
                              batchSize=4)


NINE_LAYERS = {**hybrid_moe_tiny.TINY, "layer_types": [
    "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv"]}
EVERY_EXPERT = {**latent_moe_tiny.TINY, "experts_held": 16, "expert_rank": 0}


@pytest.mark.parametrize("spec,layers", [
    (NINE_LAYERS, 8), (hybrid_moe_tiny.TINY, 4), (latent_moe_tiny.TINY, 0),
    (EVERY_EXPERT, 4)], ids=["hybrid_9", "hybrid_5", "latent_share",
                             "latent_every_expert"])
def test_metrics_carry_the_layers_that_combine_by_gather(spec, layers):
    from mmlspark_tpu.core.table import DataTable
    model = _served(spec)
    assert model.metrics()["moe_gather_combines"] == layers
    rows = hybrid_moe_tiny.ROWS.astype(np.float32)
    model.transform(DataTable({"features": rows}))
    assert model.metrics()["moe_gather_combines"] == layers
    # ... and the step the model compiled holds as many scatter-adds as
    # expert layers that do not
    module = model.get("modelFn").module
    prims = _primitives(jax.make_jaxpr(lambda p, t: module.apply(
        {"params": p}, t))(model.get("weights")["params"],
                           jnp.asarray(hybrid_moe_tiny.ROWS)).jaxpr)
    expert_layers = 8 if spec is NINE_LAYERS else 4
    assert prims.count("scatter-add") == expert_layers - layers


def test_a_model_with_no_expert_layer_reads_zero_and_exports_it():
    import flax.linen as nn
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    from mmlspark_tpu.models.tpu_model import TPUModel
    dense = nn.Dense(3)
    params = dense.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    model = TPUModel.from_flax(dense, params, inputCol="features",
                               outputCol="scores")
    assert model.metrics()["moe_gather_combines"] == 0
    assert TPUModel.from_fn(lambda w, x: x["features"], {}).metrics()[
        "moe_gather_combines"] == 0
    r = PromRenderer()
    pipeline_families(r, _served(hybrid_moe_tiny.TINY), {})
    assert "serving_model_moe_gather_combines 4" in r.render()


TINY_WIDTHS = {"vocab_size": 128, "max_len": 32, "hidden_size": 64,
               "num_attention_heads": 8, "num_key_value_heads": 2,
               "head_dim": 16, "sliding_window": 8, "intermediate_size": 128,
               "moe_intermediate_size": 32, "num_experts": 16}
GLM_WIDTHS = {k: v for k, v in latent_moe_tiny.TINY.items()
              if k not in ("indexer_types", "mlp_layer_types")}
# the benchmark's configurations, their layers kept and their widths cut
CONFIGS = {
    "lfm2-24b-a2b-stage": (TINY_WIDTHS, 8),
    "mellum2-12b-a2.5b-stage": (TINY_WIDTHS, 8),
    "glm-5.2-ep16": (GLM_WIDTHS, 0),
    "gpt2-xl": ({"vocab_size": 64, "dim": 32, "depth": 2, "heads": 2,
                 "max_len": 32, "num_classes": 4}, 0),
}


@pytest.mark.parametrize("config", CONFIGS)
def test_metrics_carry_the_layers_whose_down_product_runs_once(config):
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    widths, layers = CONFIGS[config]
    model = _served({**_spec(config), **widths})
    assert model.metrics()["moe_layer_down_products"] == layers
    assert model.metrics()["moe_gather_combines"] == layers
    r = PromRenderer()
    pipeline_families(r, model, {})
    assert f"serving_model_moe_layer_down_products {layers}" in r.render()


@pytest.mark.parametrize("config", CONFIGS)
def test_metrics_carry_the_layers_whose_passes_are_one_kernel(config):
    """``moe_fused_swiglu_layers``: the expert layers whose passes run
    gate, up and silu * up as ``grouped_swiglu``: the branch that
    combines by a gather, so 8, 8, 0 and 0."""
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    widths, layers = CONFIGS[config]
    model = _served({**_spec(config), **widths})
    assert model.metrics()["moe_fused_swiglu_layers"] == layers
    assert model.metrics()["moe_layer_down_products"] == layers
    r = PromRenderer()
    pipeline_families(r, model, {})
    assert f"serving_model_moe_fused_swiglu_layers {layers}" in r.render()


@pytest.mark.parametrize("config,layers", [
    ("lfm2-24b-a2b-stage", 8), ("mellum2-12b-a2.5b-stage", 8),
    ("trinity-mini-stage", 4), ("glm-5.2-ep16", 0), ("gpt2-xl", 0)])
def test_metrics_carry_the_layers_whose_passes_read_rows_through_ids(
        config, layers):
    """``moe_row_fetch_layers``: the expert layers whose passes read
    their rows through their token ids inside ``grouped_swiglu`` (no
    dispatch gather): every expert held, so 8, 8, 4, 0 and 0."""
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    widths = CONFIGS.get(config, (TINY_WIDTHS,))[0]
    model = _served({**_spec(config), **widths})
    assert model.metrics()["moe_row_fetch_layers"] == layers
    assert model.metrics()["moe_fused_swiglu_layers"] == layers
    r = PromRenderer()
    pipeline_families(r, model, {})
    assert f"serving_model_moe_row_fetch_layers {layers}" in r.render()
