"""The names that the benchmark's readers find things by, pinned.

``trace_reduce`` matches the flash kernels by the names of their custom
calls, which come from the jitted wrappers of ops/flash_attention.py;
a profile shows the learner's and the scorer's programs under the names
of the functions they jit. A rename of either would silence a reader on
the chip and nowhere else, so: the flash forward and backward are
compiled here for a v5e, at the train cell's shape, and the programs are
lowered on the CPU.

This is the one test file that describes a TPU topology. It does so in
a fixture, never at import time (a worker that loads the TPU's library
keeps it, and every worker imports every test file).
"""

import importlib.util
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_pinned_names")
import trace_reduce   # noqa: E402  (run.py put benchmark/ on the path)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flash_step_text(one_chip):
    """The compiled text of a flash forward and backward at the train
    cell's shape: batch 8, 16 heads of 64, 1024 tokens, bfloat16."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from mmlspark_tpu.ops.flash_attention import flash_attention
    cell = run.load_cell(ROOT, "gpt2m_train")
    spec, tr = cell["config_file"]["networkSpec"], cell["traffic_file"]
    x = jax.ShapeDtypeStruct(
        (tr["batch"], spec["max_len"], spec["heads"],
         spec["dim"] // spec["heads"]), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    # a compile for a described chip cannot be read back from the
    # persistent cache, and warns: keep it out of there
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("pattern,calls", [
    ("FLASH_FORWARD", 1), ("FLASH_BACKWARD", 2)])
def test_flash_custom_calls_match_the_readers_patterns(
        flash_step_text, pattern, calls):
    rx = re.compile(getattr(trace_reduce, pattern))
    hits = [ln.strip() for ln in flash_step_text.splitlines()
            if rx.search(ln.strip())]
    assert len(hits) == calls, hits
    assert all('custom_call_target="tpu_custom_call"' in h for h in hits)
    # and nothing else of the step is read as that kernel
    other = "FLASH_BACKWARD" if pattern == "FLASH_FORWARD" \
        else "FLASH_FORWARD"
    assert not any(re.search(getattr(trace_reduce, other), h)
                   for h in hits)


# --------------------------------------------------- program names, on the CPU

def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_scorer_program_is_named_tpu_model_forward():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.tpu_model import TPUModel
    model = TPUModel.from_fn(lambda w, ins: ins["x"] @ w,
                             jnp.ones((4, 2)), inputCol="x",
                             outputCol="y", batchSize=8)
    lowered = model._compiled().lower(
        jnp.ones((4, 2)), {"x": jnp.ones((8, 4))})
    assert _module_name(lowered) == "jit_tpu_model_forward"
    assert jax.default_backend() == "cpu"


@pytest.mark.parametrize("feed,program", [
    ("device", "jit_learner_chunk"), ("host", "jit_learner_step")])
def test_learner_programs_are_named(monkeypatch, feed, program):
    """``fit`` jits its step under the name the profile shows: seen at
    ``jax.jit`` as the learner calls it."""
    import jax
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.learner import TPULearner
    named = []
    real_jit = jax.jit

    def spy(fun, *a, **kw):
        named.append("jit_" + fun.__name__)
        return real_jit(fun, *a, **kw)
    monkeypatch.setattr(jax, "jit", spy)
    rng = np.random.default_rng(0)
    TPULearner(networkSpec={"type": "mlp", "features": [4],
                            "num_classes": 2},
               epochs=1, batchSize=16, computeDtype="float32",
               dataFeed=feed).fit(DataTable({
                   "features": rng.normal(size=(32, 4)).astype(np.float32),
                   "label": rng.integers(0, 2, 32).astype(np.int64)}))
    assert program in named, named
    assert "jit_f" not in named and "jit_run" not in named


def test_benchmark_reads_no_program_by_its_old_name():
    """The main program is picked by device time, not by name."""
    for dirpath, _, files in os.walk(BENCH_DIR):
        for f in files:
            if f.endswith((".py", ".json")):
                text = open(os.path.join(dirpath, f)).read()
                assert not re.search(r"\bjit_(f|run)\b", text), f
