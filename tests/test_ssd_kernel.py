"""The chunked SSD scan as one Pallas kernel (``ops/ssd_scan._ssd_kernel``)
in interpreter mode on the CPU: against the sequential recurrence and
against the ``jax.numpy`` steps, y and the final states, at 1, 2 and 4
chunks and at a length no multiple of the chunk (padded with dt = 0),
at two head-block widths, with and without the D skip, a group or two,
two and four heads a tile of 128 lanes; the choice of the kernel for
each backend and shape it rules on; one trace of the kernel for every
layer of a shape; a Mamba-2 model through the kernel against the same
model through the ``jax.numpy`` steps; and the counter
``ssm_scan_kernel_layers``."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import ssd_scan as scan

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from granite_tiny import TINY, build  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(key, length, heads=4, p=64, n=128, groups=1, batch=2,
             dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (batch, length, heads, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads))
                         - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0,
                                    maxval=2.7))
    b, c = (jax.random.normal(k, (batch, length, groups, n), dtype)
            for k in ks[3:5])
    return x, dt, a, b, c


def kernel(*args, chunk, block):
    return scan._ssd_kernel(*args, chunk=chunk, block=block,
                            interpret=True)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# (length, chunk): 1, 2 and 4 chunks, and 300 = two chunks of 128 and a
# third padded
LENGTHS = {"one_chunk": (128, 128), "two_chunks": (256, 128),
           "four_chunks": (512, 128), "padded": (300, 128),
           "two_chunks_of_256": (512, 256)}
# (heads, P, groups, block): two block widths of two heads a tile, two
# groups, four heads a tile
HEADS = {"block_of_2": (4, 64, 1, 2), "block_of_4": (4, 64, 1, 4),
         "two_groups": (4, 64, 2, 2), "four_a_tile": (8, 32, 1, 8)}


@pytest.mark.parametrize("skip", [True, False], ids=["d", "no_d"])
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_the_kernel_against_the_jnp_steps_and_the_recurrence(length, heads,
                                                            skip):
    """bfloat16 x, B and C as the model hands them: the kernel rounds as
    the ``jax.numpy`` steps do (the same operands in bfloat16, the same
    float32 decays and sums), but for the order of float32 sums and the
    running sums' own rounding once scaled by log2 e (~1e-5 of a decay,
    as large as their rounding in the steps), which now and then rounds
    an operand to the neighbouring bfloat16: the two differ by 1e-6 to
    4e-5 in y and up to 3e-4 in the final states, where either is
    2e-3 from the recurrence, and neither is the nearer to it."""
    length, chunk = LENGTHS[length]
    h, p, groups, block = HEADS[heads]
    x, dt, a, b, c = operands(1, length, h, p, groups=groups)
    d = jnp.linspace(-1.0, 2.0, h) if skip else None
    y, final = kernel(x, dt, a, b, c, d, chunk=chunk, block=block)
    assert y.shape == x.shape and final.shape == (2, h, p, 128)
    assert y.dtype == final.dtype == jnp.float32
    steps_y, steps_final = scan._chunked(x, dt, a, b, c, chunk, d)
    assert rel(y, steps_y) < 2e-4 and rel(final, steps_final) < 1e-3
    want, state = scan.recurrence(x, dt, a, b, c, d)
    assert rel(y, want) < 0.01 and rel(final, state) < 0.01
    assert abs(rel(y, want) - rel(steps_y, want)) < 2e-4 * rel(y, want) \
        + 1e-6


@pytest.mark.parametrize("length", ["two_chunks", "padded"])
def test_in_float32_the_kernel_is_the_recurrence(length):
    length, chunk = LENGTHS[length]
    x, dt, a, b, c = operands(2, length, dtype=jnp.float32)
    d = jnp.asarray([1.0, 0.5, -0.3, 2.0])
    y, final = kernel(x, dt, a, b, c, d, chunk=chunk, block=2)
    want, state = scan.recurrence(x, dt, a, b, c, d)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(final, state, rtol=2e-4, atol=2e-4)


def test_padded_positions_carry_no_step():
    """The final state of a row of 300 is that of its 300th position:
    the last 44 positions count, and 84 more of dt = 0 (x = 1) change
    nothing."""
    x, dt, a, b, c = operands(3, 300)
    _, final = kernel(x, dt, a, b, c, None, chunk=128, block=4)
    _, cut = kernel(x[:, :256], dt[:, :256], a, b[:, :256], c[:, :256],
                    None, chunk=128, block=4)
    assert rel(final, cut) > 0.01

    def longer(v, value=0.0):
        return jnp.pad(v, [(0, 0), (0, 84)] + [(0, 0)] * (v.ndim - 2),
                       constant_values=value)
    _, padded = kernel(longer(x, 1.0), longer(dt), a, longer(b, 1.0),
                       longer(c, 1.0), None, chunk=128, block=4)
    np.testing.assert_allclose(final, padded, rtol=1e-6, atol=1e-6)


def test_a_state_carried_over_the_chunks_is_part_of_the_answer():
    x, dt, a, b, c = operands(4, 256)
    y, _ = kernel(x, dt, a, b, c, None, chunk=128, block=4)
    second, _ = kernel(*(v[:, 128:] for v in (x, dt)), a,
                       *(v[:, 128:] for v in (b, c)), None, chunk=128,
                       block=4)
    assert rel(y[:, 128:], scan._chunked(
        x, dt, a, b, c, 128, None)[0][:, 128:]) < 2e-4
    assert rel(second, y[:, 128:]) > 0.01


# ------------------------------------------------------------ the choice

GRANITE = (64, 64, 128, 1, 256)      # heads, P, N, groups, chunk


@pytest.mark.parametrize("backend, shape, runs", [
    ("tpu", GRANITE, True),
    ("cpu", GRANITE, False),
    ("gpu", GRANITE, False),
    ("tpu", (64, 64, 128, 1, 64), False),      # chunk no multiple of 128
    ("tpu", (64, 64, 16, 1, 256), False),      # N no multiple of 128
    ("tpu", (64, 96, 128, 1, 256), False),     # P does not divide 128
    ("tpu", (64, 256, 128, 1, 256), False),
    ("tpu", (4, 16, 128, 4, 128), False),      # a group's heads: 16 lanes
    ("tpu", (8, 64, 128, 2, 128), True),       # a block inside a group
    ("tpu", (8, 16, 16, 1, 8), False),         # the tests' tiny preset
], ids=["granite", "cpu", "gpu", "chunk_64", "state_16", "p_96", "p_256",
        "group_of_16_lanes", "two_groups", "tiny_preset"])
def test_where_the_kernel_runs(monkeypatch, backend, shape, runs):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert scan.kernel_runs(*shape) is runs


@pytest.mark.parametrize("heads, p, groups, block", [
    (64, 64, 1, 64), (4, 64, 1, 4), (4, 64, 2, 2), (256, 64, 1, 128),
    (8, 32, 1, 8), (6, 64, 1, 6), (3, 64, 1, 0), (4, 16, 4, 0)])
def test_the_head_block(heads, p, groups, block):
    """The most heads up to 128 that divide a group's and fill whole
    tiles of 128 lanes; 0 where none do."""
    assert scan.head_block(heads, p, groups) == block


def _walk(jaxpr):
    """Every equation, into every sub-jaxpr but a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk(sub)


def test_on_a_tpu_ssd_scan_is_the_kernel(monkeypatch):
    """The contract's call on a TPU: one kernel and no (T, T) value
    outside it; elsewhere no kernel at all."""
    x, dt, a, b, c = operands(5, 512)
    d = jnp.ones((4,))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(lambda *v: scan.ssd_scan(*v, 256, d))(
        x, dt, a, b, c).jaxpr
    names = [e.primitive.name for e in _walk(jaxpr)]
    assert names.count("pallas_call") == 1
    outside = [v.aval.shape for e in _walk(jaxpr) for v in e.outvars
               if e.primitive.name != "pallas_call"]
    assert not [s for s in outside if s[-2:] == (256, 256)]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    jaxpr = jax.make_jaxpr(lambda *v: scan.ssd_scan(*v, 256, d))(
        x, dt, a, b, c).jaxpr
    assert "pallas_call" not in [e.primitive.name for e in _walk(jaxpr)]


# a Granite-kind model whose scan the kernel takes: three Mamba-2 layers
# of 4 heads of 64 channels, a state of 128, chunks of 128 over rows of
# 300 (the last chunk padded)
KERNEL_SIZED = dict(hidden_size=128, max_len=300, mamba_n_heads=4,
                    mamba_d_head=64, mamba_d_state=128,
                    mamba_chunk_size=128)


def test_the_kernel_is_traced_once_for_every_layer_of_a_shape(monkeypatch):
    """One ``pallas_call`` traced for the model's three Mamba-2 layers:
    the kernel is one jitted function, built once a shape, and the
    whole step calls it three times."""
    from jax.experimental import pallas as pl
    from mmlspark_tpu.models.networks import build_network
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module = build_network({"dtype": "bfloat16", **TINY, **KERNEL_SIZED,
                            "max_len": 296})
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    traced, real = [], pl.pallas_call

    def counted(*a, **k):
        traced.append(k.get("name"))
        return real(*a, **k)
    monkeypatch.setattr(pl, "pallas_call", counted)
    jaxpr = jax.make_jaxpr(lambda v, t: module.apply(v, t))(
        variables, jax.ShapeDtypeStruct((2, 296), jnp.int32)).jaxpr
    assert traced == ["ssd_scan"]
    calls = [e for e in _walk(jaxpr) if e.primitive.name == "jit"
             and e.params["name"] == "_ssd_kernel"]
    assert len(calls) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    assert [e.primitive.name for e in _walk(jaxpr)].count("pallas_call") \
        == 3


def test_a_model_through_the_kernel_is_the_model_through_the_steps(
        monkeypatch):
    """The same seeded model, its scan once the kernel (interpreted)
    and once the ``jax.numpy`` steps: the logits and every Mamba-2
    operator agree to the order of float32 sums."""
    from mmlspark_tpu.models.networks import build_network
    module = build_network({"dtype": "float32", **TINY, **KERNEL_SIZED})
    params = jax.jit(module.init)(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    rows = jnp.asarray(np.random.default_rng(1).integers(0, 128, (2, 300)))

    def run(**kw):
        return np.asarray(module.apply({"params": params}, rows, **kw))
    steps = [run(), run(capture="operator_0"), run(capture="operator_3")]
    monkeypatch.setattr(scan, "kernel_runs", lambda *a: True)
    monkeypatch.setattr(scan, "_ssd_kernel",
                        functools.partial(scan._ssd_kernel, interpret=True))
    through = [run(), run(capture="operator_0"), run(capture="operator_3")]
    for got, want in zip(through, steps):
        assert rel(got, want) < 1e-4


# ------------------------------------------------------------ the counter

def test_the_counter_on_the_cell_s_configuration(monkeypatch):
    """``ssm_scan_kernel_layers``: Granite's 36 Mamba-2 layers on a TPU,
    0 off it; 0 for every other configuration of the benchmark."""
    from mmlspark_tpu.models.networks import build_network

    def spec(name):
        return json.load(open(os.path.join(
            ROOT, "benchmark", "configs", name + ".json")))["networkSpec"]
    granite = build_network({"dtype": "bfloat16",
                             **spec("granite-4.0-h-micro")})
    assert granite.ssm_scan_kernel_layers == 0      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert granite.ssm_scan_kernel_layers == granite.ssm_layers == 36
    for name in ("lfm2-24b-a2b-stage", "mellum2-12b-a2.5b-stage",
                 "trinity-mini-stage", "glm-5.2-ep16", "gpt2-xl",
                 "gpt2-medium"):
        module = build_network({"dtype": "bfloat16", **spec(name)})
        assert getattr(module, "ssm_scan_kernel_layers", 0) == 0, name


@pytest.mark.parametrize("backend, layers", [("tpu", 3), ("cpu", 0)])
def test_the_counter_through_tpu_model(monkeypatch, backend, layers):
    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    from mmlspark_tpu.models.tpu_model import TPUModel
    module, params = build(gains=False, **KERNEL_SIZED)
    model = TPUModel.from_flax(module, {"params": params},
                               inputCol="features", outputCol="scores",
                               batchSize=2)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    m = model.metrics()
    assert (m["ssm_layers"], m["ssm_scan_kernel_layers"]) == (3, layers)
    r = PromRenderer()
    pipeline_families(r, model, {})
    assert f"serving_model_ssm_scan_kernel_layers {layers}" in r.render()
    small, small_params = build(gains=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiny = TPUModel.from_flax(small, {"params": small_params},
                              inputCol="features", outputCol="scores",
                              batchSize=2)
    assert tiny.metrics()["ssm_scan_kernel_layers"] == 0


@pytest.mark.parametrize("batch", [4, 3])   # 3 does not divide: replicated
@pytest.mark.parametrize("skip", [True, False], ids=["d", "no_d"])
def test_under_a_mesh_the_kernel_runs_per_shard(cpu_mesh_devices, batch,
                                                skip):
    """XLA cannot partition a Mosaic kernel, so under a jit over several
    devices the kernel runs per shard by ``flash_per_shard`` (rows over
    the data axis, a and D whole): the same y and final states as the
    unsharded steps."""
    from mmlspark_tpu.parallel import mesh as mesh_lib
    from mmlspark_tpu.parallel.ring_attention import flash_per_shard
    x, dt, a, b, c = operands(6, 256, batch=batch)
    d = jnp.linspace(-1.0, 2.0, 4) if skip else None

    def local(x, dt, a, b, c, d):
        return kernel(x, dt, a, b, c, d, chunk=128, block=2)
    with jax.set_mesh(mesh_lib.make_mesh({"data": 4, "fsdp": 2})):
        y, final = jax.jit(lambda *v: flash_per_shard(
            local, jax.sharding.get_abstract_mesh(), *v, whole=(2, 5)))(
            x, dt, a, b, c, d)
    steps_y, steps_final = scan._chunked(x, dt, a, b, c, 128, d)
    assert rel(y, steps_y) < 2e-4 and rel(final, steps_final) < 1e-3


@pytest.mark.parametrize("batch", [4, 3])   # 3 does not divide: replicated
def test_a_model_under_a_mesh_runs_its_scans_per_shard(
        cpu_mesh_devices, monkeypatch, batch):
    """``Mamba2Mixer`` under a jit over eight devices: each of the three
    scans is a kernel in a ``shard_map``, and the logits are the model's
    through the ``jax.numpy`` steps with no mesh."""
    from mmlspark_tpu.models.networks import build_network
    from mmlspark_tpu.parallel import mesh as mesh_lib
    module = build_network({"dtype": "float32", **TINY, **KERNEL_SIZED})
    params = jax.jit(module.init)(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))
    rows = jnp.asarray(np.random.default_rng(2).integers(0, 128,
                                                         (batch, 300)))
    want = module.apply(params, rows)
    monkeypatch.setattr(scan, "kernel_runs", lambda *a: True)
    monkeypatch.setattr(scan, "_ssd_kernel",
                        functools.partial(scan._ssd_kernel, interpret=True))
    with jax.set_mesh(mesh_lib.make_mesh({"data": 4, "fsdp": 2})):
        step = jax.jit(module.apply)
        jaxpr = jax.make_jaxpr(step)(params, rows).jaxpr
        got = step(params, rows)
    names = [e.primitive.name for e in _walk(jaxpr)]
    assert names.count("shard_map") == names.count("pallas_call") == 3
    assert rel(got, want) < 1e-4
