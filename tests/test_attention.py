"""Attention ops/layers + ring-attention sequence parallelism.

Reference test parity: the attention layer gradchecks live in DL4J's
AttentionLayerTest (deeplearning4j-core gradientcheck suite); the op itself is
covered by libnd4j DeclarableOpsTests + SameDiff opvalidation. Ring attention
has NO reference counterpart (SURVEY.md §5.7) — validated against the exact
op on the 8-virtual-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import gradcheck
from deeplearning4j_tpu.nn.attention import (
    LearnedSelfAttentionLayer,
    RecurrentAttentionLayer,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.ops import attention as A


def _qkv(rng, b=2, h=2, s=64, d=16, scale=0.3):
    return tuple(
        jnp.asarray(rng.normal(size=(b, h, s, d)) * scale, jnp.float32)
        for _ in range(3)
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_exact_jnp(self, rng, causal):
        q, k, v = _qkv(rng)
        ref = A.dot_product_attention(q, k, v, causal=causal)
        out = A.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                                use_pallas=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_exact_pallas_interpret(self, rng, causal):
        q, k, v = _qkv(rng, s=32, d=8)
        ref = A.dot_product_attention(q, k, v, causal=causal)
        out = A.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                                use_pallas="interpret")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_cross_attention_lengths(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 2, 32, 16)) * 0.3, jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 2, 64, 16)) * 0.3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 2, 64, 16)) * 0.3, jnp.float32)
        ref = A.dot_product_attention(q, k, v, causal=True)
        out = A.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                                use_pallas=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_exact(self, rng, causal):
        q, k, v = _qkv(rng, s=32, d=8)

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(A.dot_product_attention(q, k, v, causal=causal)))

        def loss_flash(q, k, v):
            return jnp.sum(jnp.sin(A.flash_attention(
                q, k, v, causal=causal, block_q=16, block_k=16, use_pallas=False)))

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3)

    def test_gradients_fully_masked_rows(self, rng):
        # causal with Sq > Sk: early query rows attend to nothing; their
        # forward output is zero and their gradient mass must be zero too
        q = jnp.asarray(rng.normal(size=(1, 2, 4, 8)) * 0.3, jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, 2, 8)) * 0.3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, 2, 8)) * 0.3, jnp.float32)

        scale = 1.0 / np.sqrt(8)

        def loss_ad(q, k, v):
            # autodiff straight through the blockwise forward (no custom VJP)
            out, _ = A._flash_fwd_jnp(q, k, v, scale, True, 2)
            return jnp.sum(jnp.sin(out))

        def loss_flash(q, k, v):
            return jnp.sum(jnp.sin(A.flash_attention(
                q, k, v, causal=True, block_q=2, block_k=2, use_pallas=False)))

        g_ad = jax.grad(loss_ad, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ad, g_fl):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3)

    def test_padding_mask_matches_manual_softmax(self, rng):
        q, k, v = _qkv(rng, s=8, d=4)
        mask = jnp.asarray(rng.integers(0, 2, size=(2, 1, 1, 8)), bool)
        mask = mask.at[..., 0].set(True)
        out = A.dot_product_attention(q, k, v, mask=mask)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(4)
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        np.testing.assert_allclose(out, jnp.einsum("bhqk,bhkv->bhqv", w, v),
                                   atol=1e-6)


class TestMultiHeadOp:
    def test_shapes_and_mask(self, rng):
        b, t, f, hd = 2, 12, 10, 16
        x = jnp.asarray(rng.normal(size=(b, t, f)) * 0.5, jnp.float32)
        Wq, Wk, Wv = (jnp.asarray(rng.normal(size=(f, hd)) * 0.2, jnp.float32)
                      for _ in range(3))
        Wo = jnp.asarray(rng.normal(size=(hd, f)) * 0.2, jnp.float32)
        mask = jnp.ones((b, t)).at[0, 6:].set(0)
        out = A.multi_head_dot_product_attention(
            x, x, x, Wq, Wk, Wv, Wo, n_heads=4, mask=mask)
        assert out.shape == (b, t, f)
        # masked keys/values must not influence valid-row outputs
        x2 = x.at[0, 6:].add(100.0)
        out3 = A.multi_head_dot_product_attention(
            x, x2, x2, Wq, Wk, Wv, Wo, n_heads=4, mask=mask)
        np.testing.assert_allclose(out3[0, :6], out[0, :6], atol=1e-4)


class TestAttentionLayers:
    def test_self_attention_gradcheck(self, rng):
        layer = SelfAttentionLayer(n_in=6, n_out=8, n_heads=2)
        params, state = layer.initialize(jax.random.PRNGKey(0), (5, 6))
        x = jnp.asarray(rng.standard_normal((2, 5, 6)))

        def loss(p):
            y, _ = layer.apply(p, state, x.astype(jax.tree_util.tree_leaves(p)[0].dtype))
            return jnp.sum(y ** 2)

        res = gradcheck.check_model_gradients(loss, params, eps=1e-4)
        assert res.passed, res

    # slow: over a minute by itself (an fp64 gradcheck through the
    # recurrent-attention scan); test_self_attention_gradcheck above is the
    # tier-1 test of the attention layers' gradients
    @pytest.mark.slow
    def test_recurrent_attention_gradcheck(self, rng):
        layer = RecurrentAttentionLayer(n_in=4, n_out=6, n_heads=2)
        params, state = layer.initialize(jax.random.PRNGKey(1), (5, 4))
        x = jnp.asarray(rng.standard_normal((2, 5, 4)))

        def loss(p):
            y, _ = layer.apply(p, state, x.astype(jax.tree_util.tree_leaves(p)[0].dtype))
            return jnp.sum(y ** 2)

        res = gradcheck.check_model_gradients(loss, params, eps=1e-4)
        assert res.passed, res

    def test_learned_queries_shape(self, rng):
        layer = LearnedSelfAttentionLayer(n_in=6, n_out=8, n_heads=2, n_queries=3)
        params, state = layer.initialize(jax.random.PRNGKey(0), (10, 6))
        x = jnp.asarray(rng.standard_normal((4, 10, 6)), jnp.float32)
        y, _ = layer.apply(params, state, x)
        assert y.shape == (4, 3, 8)
        assert layer.output_shape((10, 6)) == (3, 8)

    def test_unprojected_requires_square(self):
        with pytest.raises(ValueError):
            SelfAttentionLayer(n_in=4, n_out=6, project_input=False).initialize(
                jax.random.PRNGKey(0), (5, 4))

    def test_self_attention_mask_blocks_padding(self, rng):
        layer = SelfAttentionLayer(n_in=4, n_out=4, n_heads=1)
        params, state = layer.initialize(jax.random.PRNGKey(0), (6, 4))
        x = jnp.asarray(rng.standard_normal((1, 6, 4)), jnp.float32)
        mask = jnp.asarray([[1, 1, 1, 0, 0, 0]], jnp.float32)
        y, _ = layer.apply(params, state, x, mask=mask)
        x2 = x.at[:, 3:].add(50.0)
        y2, _ = layer.apply(params, state, x2, mask=mask)
        np.testing.assert_allclose(y[:, :3], y2[:, :3], atol=1e-4)
        np.testing.assert_allclose(y[:, 3:], 0.0, atol=1e-6)

    def test_in_multilayer_network(self, rng):
        from deeplearning4j_tpu.nn import MultiLayerNetwork
        from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import GlobalPoolingLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        conf = (
            NeuralNetConfiguration.builder()
            .seed(0)
            .updater(Adam(0.01))
            .list()
            .layer(SelfAttentionLayer(n_in=5, n_out=8, n_heads=2))
            .layer(GlobalPoolingLayer(pooling_type="max"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.recurrent(5, 7))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        x = rng.standard_normal((4, 7, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
        s0 = net.score(x=x, y=y)
        for _ in range(30):
            net._fit_batch(x, y)
        assert net.score(x=x, y=y) < s0
        out = net.output(x)
        assert out.shape == (4, 3)


@pytest.mark.multichip
class TestRingAttention:
    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:8]).reshape(8), ("seq",))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_exact(self, rng, causal):
        from deeplearning4j_tpu.parallel import ring_attention, shard_sequence

        mesh = self._mesh()
        q, k, v = _qkv(rng, b=2, h=2, s=64, d=8)
        ref = A.dot_product_attention(q, k, v, causal=causal)
        qs, ks, vs = (shard_sequence(t, mesh) for t in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("seq", [512, 1024])
    def test_long_sequence_8way(self, rng, seq):
        """VERDICT r2 next-round #5: ring attention at seq >= 512 with 8-way
        sequence sharding (64/128 tokens per shard), value-checked vs exact."""
        from deeplearning4j_tpu.parallel import ring_attention, shard_sequence

        mesh = self._mesh()
        q, k, v = _qkv(rng, b=1, h=2, s=seq, d=16)
        ref = A.dot_product_attention(q, k, v, causal=True)
        qs, ks, vs = (shard_sequence(t, mesh) for t in (q, k, v))
        out = jax.jit(
            lambda a, b, c: ring_attention(a, b, c, mesh, causal=True)
        )(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(out), ref, atol=5e-5, rtol=1e-3)

    def test_gradients_match_exact(self, rng):
        from deeplearning4j_tpu.parallel import ring_attention, shard_sequence

        mesh = self._mesh()
        q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(A.dot_product_attention(q, k, v, causal=True)))

        def loss_ring(q, k, v):
            return jnp.sum(jnp.sin(ring_attention(q, k, v, mesh, causal=True)))

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        qs, ks, vs = (shard_sequence(t, mesh) for t in (q, k, v))
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
        for a, b in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(b), a, atol=5e-5, rtol=1e-3)


class TestFlashAutoDispatch:
    """Auto-dispatch by sequence length (ops.attention.FLASH_MIN_SEQ)."""

    def test_resolve_flash_rules(self):
        rf = A.resolve_flash
        # full [B,1|H,Tq,Tk] attention masks force the exact path; (B,Tk)
        # PADDING masks are flash-eligible since r14 (the kernel masks key
        # blocks in-place — equivalence pinned in tests/test_kernels.py)
        assert rf(True, 4096, 4096,
                  mask=jnp.ones((2, 1, 4096, 4096))) is False
        assert rf(True, 4096, 4096, mask=jnp.ones((2, 4096))) is True
        # explicit booleans are respected
        assert rf(True, 128, 128) is True
        assert rf(False, 4096, 4096) is False
        # "auto" on CPU never picks the (jnp fallback) flash path
        assert rf("auto", 4096, 4096) is (jax.default_backend() == "tpu")
        assert rf("auto", 128, 128) is False  # below crossover everywhere

    def test_mha_auto_matches_exact(self, rng):
        """flash="auto" (default) must be numerically identical to the exact
        path at short seq — it IS the exact path below the crossover."""
        F, H = 8, 2
        x = jnp.asarray(rng.normal(size=(2, 6, F)).astype(np.float32))
        Ws = [jnp.asarray(rng.normal(size=(F, F)).astype(np.float32) * 0.3)
              for _ in range(4)]
        auto = A.multi_head_dot_product_attention(x, x, x, *Ws, n_heads=H)
        exact = A.multi_head_dot_product_attention(x, x, x, *Ws, n_heads=H,
                                                   flash=False)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(exact))

    def test_resolve_flash_rejects_typos(self):
        with pytest.raises(ValueError, match="flash"):
            A.resolve_flash("Auto", 2048, 2048)

    def test_sequence_mask_jit_needs_maxlen(self):
        from deeplearning4j_tpu import ops
        with pytest.raises(ValueError, match="maxlen"):
            jax.jit(lambda l: ops.exec_op("sequence_mask", l))(
                jnp.asarray([1, 3]))
        m = jax.jit(lambda l: ops.exec_op("sequence_mask", l, 4))(
            jnp.asarray([1, 3]))
        assert m.shape == (2, 4)
