"""Ring attention / Ulysses sequence-parallelism tests.

Long-context support is new capability beyond the reference
(ref: SURVEY.md §5 — it has none); correctness bar: seq-parallel
attention must match dense attention to float tolerance in BOTH forward
and backward on the virtual 8-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.models.networks import Transformer
from mmlspark_tpu.parallel import mesh as mesh_lib
from mmlspark_tpu.parallel.ring_attention import (
    attention, make_seq_parallel_attention, make_seq_parallel_train_step,
    ring_attention, seq_parallel_apply, ulysses_attention,
)


def _qkv(B=2, L=64, H=8, D=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
                 for _ in range(3))


@pytest.fixture(scope="module")
def seq_mesh(cpu_mesh_devices):
    return mesh_lib.make_mesh({"seq": 8})


class TestForward:
    @pytest.mark.parametrize("kind", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, seq_mesh, kind, causal):
        q, k, v = _qkv()
        ref = attention(q, k, v, causal=causal)
        fn = make_seq_parallel_attention(seq_mesh, kind=kind,
                                         causal=causal)
        out = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    def test_ulysses_requires_divisible_heads(self, seq_mesh):
        q, k, v = _qkv(H=4)  # 4 heads, 8 devices
        fn = make_seq_parallel_attention(seq_mesh, kind="ulysses")
        with pytest.raises(ValueError, match="divisible"):
            fn(q, k, v)

    def test_long_sequence_shards(self, seq_mesh):
        # 1024 tokens over 8 devices = 128/device
        q, k, v = _qkv(B=1, L=1024, H=8, D=8)
        ref = attention(q, k, v, causal=True)
        out = make_seq_parallel_attention(seq_mesh, causal=True)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)


class TestBackward:
    def test_ring_vjp_matches_dense(self, cpu_mesh_devices):
        mesh = mesh_lib.make_mesh({"seq": 4},
                                  devices=jax.devices()[:4])
        q, k, v = _qkv(B=1, L=16, H=2, D=8)
        w = jnp.asarray(np.random.default_rng(9).normal(
            size=(1, 16, 2, 8)), jnp.float32)

        def local_loss(q, k, v, w):
            out = ring_attention(q, k, v, axis_name="seq", causal=True)
            return jnp.sum(out * w)  # local; global loss = implicit sum

        gf = jax.jit(shard_map(
            lambda q, k, v, w: jax.grad(local_loss, argnums=(0, 1, 2))(
                q, k, v, w),
            mesh=mesh, in_specs=(P(None, "seq"),) * 4,
            out_specs=(P(None, "seq"),) * 3, check_vma=False))
        gq, gk, gv = gf(q, k, v, w)

        def dense_loss(q, k, v):
            return jnp.sum(attention(q, k, v, causal=True) * w)

        gq_r, gk_r, gv_r = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in [(gq, gq_r), (gk, gk_r), (gv, gv_r)]:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


class TestTransformerSeqParallel:
    def _model_pair(self, L, impl="ring", num_classes=0):
        kw = dict(vocab_size=64, dim=32, depth=2, heads=8, max_len=L,
                  num_classes=num_classes)
        return (Transformer(**kw),
                Transformer(seq_axis="seq", seq_impl=impl, **kw))

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_lm_logits_match_dense(self, seq_mesh, impl):
        L = 64
        dense, sp = self._model_pair(L, impl)
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, 64, (2, L)), jnp.int32)
        variables = dense.init(jax.random.PRNGKey(0), tokens)
        ref = dense.apply(variables, tokens)
        out = seq_parallel_apply(sp, variables, tokens, seq_mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    def test_classifier_pooling_matches(self, seq_mesh):
        L = 64
        dense, sp = self._model_pair(L, num_classes=5)
        tokens = jnp.asarray(np.random.default_rng(1).integers(
            0, 64, (2, L)), jnp.int32)
        variables = dense.init(jax.random.PRNGKey(0), tokens)
        ref = dense.apply(variables, tokens)
        out = seq_parallel_apply(sp, variables, tokens, seq_mesh)
        assert out.shape == (2, 5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    def test_global_seq_exceeding_max_len_raises(self, seq_mesh):
        # regression: dynamic_slice would silently clamp pos embeddings
        sp = Transformer(vocab_size=16, dim=16, depth=1, heads=4,
                         max_len=32, seq_axis="seq")
        dense = Transformer(vocab_size=16, dim=16, depth=1, heads=4,
                            max_len=32)
        variables = dense.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 32), jnp.int32))
        tokens = jnp.zeros((1, 64), jnp.int32)  # 64 global > max_len=32
        with pytest.raises(ValueError, match="max_len"):
            seq_parallel_apply(sp, variables, tokens, seq_mesh)

    def test_transformer_trains_via_tpu_learner(self, cpu_mesh_devices):
        # regression: registry network must be usable through TPULearner
        # (int_input capability flag, not a class-name special case)
        from mmlspark_tpu.core.table import DataTable
        from mmlspark_tpu.models.learner import TPULearner
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 16, size=(32, 8)).astype(np.float64)
        labels = rng.integers(0, 16, size=(32, 8)).astype(np.int64)
        t = DataTable({"features": toks, "label": labels})
        learner = TPULearner(
            networkSpec={"type": "transformer", "vocab_size": 16,
                         "dim": 16, "depth": 1, "heads": 4,
                         "max_len": 8},
            loss="token_cross_entropy", epochs=1, batchSize=16,
            computeDtype="float32")
        model = learner.fit(t)
        out = model.transform(t)
        assert np.isfinite(np.asarray(out["scores"][0])).all()

    def test_train_step_loss_decreases(self, cpu_mesh_devices):
        import optax
        mesh = mesh_lib.make_mesh({"data": 2, "seq": 4})
        L = 32
        dense, sp = self._model_pair(L)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 64, (4, L)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        params = dense.init(jax.random.PRNGKey(0), tokens)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)
        step = make_seq_parallel_train_step(sp, mesh, opt)
        losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9

    def test_train_step_grad_matches_dense(self, cpu_mesh_devices):
        """One step of the seq-parallel trainer == one dense step."""
        import optax
        mesh = mesh_lib.make_mesh({"data": 2, "seq": 4})
        L = 32
        dense, sp = self._model_pair(L)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 64, (4, L)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        params = dense.init(jax.random.PRNGKey(0), tokens)
        opt = optax.sgd(0.1)
        step = make_seq_parallel_train_step(sp, mesh, opt)
        p_sp, _, loss_sp = step(params, opt.init(params), tokens, targets)

        def dense_loss(p):
            logits = dense.apply(p, tokens)
            ll = jax.nn.log_softmax(logits.astype(jnp.float32))
            picked = jnp.take_along_axis(ll, targets[..., None], axis=-1)
            return -picked.mean()

        loss_ref, g = jax.value_and_grad(dense_loss)(params)
        updates, _ = opt.update(g, opt.init(params), params)
        p_ref = optax.apply_updates(params, updates)
        np.testing.assert_allclose(float(loss_sp), float(loss_ref),
                                   atol=1e-5)
        errs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), p_sp, p_ref)
        assert max(jax.tree_util.tree_leaves(errs)) < 1e-5


class TestRingFlash:
    """Every ring hop through the Pallas flash kernel
    (ring_flash_attention): no (Lq, Lk_local) score tensor exists in
    forward or backward; numerics match the dense ring."""

    def _mapped(self, mesh, causal, grad=False):
        from mmlspark_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        def fwd(q, k, v):
            return ring_flash_attention(q, k, v, axis_name="seq",
                                        causal=causal, interpret=True)

        if grad:
            def loss(q, k, v):
                out = fwd(q, k, v)
                # local sums add up to the global loss under shard_map
                return jnp.sum(out ** 2)
            run = shard_map(
                jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
                in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
                out_specs=(P(None, "seq"),) * 3, check_vma=False)
        else:
            run = shard_map(
                fwd, mesh=mesh,
                in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
                out_specs=P(None, "seq"), check_vma=False)
        return jax.jit(run)

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_dense_ring(self, seq_mesh, causal):
        q, k, v = _qkv(L=64)
        ref = attention(q, k, v, causal=causal)
        out = self._mapped(seq_mesh, causal)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, seq_mesh, causal):
        q, k, v = _qkv(L=32)

        def dense_loss(q, k, v):
            from mmlspark_tpu.parallel.ring_attention import (
                dense_attention,
            )
            return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

        ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        got = self._mapped(seq_mesh, causal, grad=True)(q, k, v)
        for r, g2 in zip(ref, got):
            np.testing.assert_allclose(np.asarray(g2), np.asarray(r),
                                       atol=2e-3, rtol=2e-3)

    def test_no_dense_scores_in_jaxpr(self, seq_mesh):
        """The point of the exercise: the traced ring step must contain
        no (B, H, Lq, Lk) or (Lq, Lk)-shaped intermediate. Every >=2D
        f32 aval in the jaxpr whose trailing dims are (Lq_local,
        Lk_local) would be a dense score block."""
        import re
        from mmlspark_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )
        # L_local (2048) far above the flash block sizes (256), so a
        # dense per-hop score block would be unmistakable in the avals
        B, L, H, D = 1, 16384, 2, 16
        l_loc = L // 8

        def fwd(q, k, v):
            return ring_flash_attention(q, k, v, axis_name="seq",
                                        causal=True, interpret=True)

        run = shard_map(
            fwd, mesh=seq_mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
            check_vma=False)
        q = jnp.zeros((B, L, H, D), jnp.float32)
        txt = str(jax.make_jaxpr(run)(q, q, q))
        hits = re.findall(rf"f32\[(?:\d+,)*{l_loc},{l_loc}\]", txt)
        assert not hits, f"dense (Lq, Lk) scores in ring jaxpr: {hits[:3]}"
