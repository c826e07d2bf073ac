"""Pallas kernels vs jnp references (interpret mode on the CPU test mesh).

Mirrors the reference's per-kernel numeric tests
(ref: tensorflow/python/kernel_tests/softmax_op_test.py etc.): forward
against a naive implementation, backward against jax.grad of the naive one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simple_tensorflow_tpu.ops.pallas import (
    flash_attention, layer_norm, quant_matmul, softmax_cross_entropy)
from simple_tensorflow_tpu.kernels import registry as kreg
from simple_tensorflow_tpu.ops.pallas.flash_attention import (
    VMEM_BUDGET, attention_xla, mha_reference, tiles, vmem_bytes)
from simple_tensorflow_tpu.ops.pallas.layer_norm import layer_norm_reference
from simple_tensorflow_tpu.ops.pallas.quant_matmul import (
    quant_matmul_reference, quantize_colwise)
from simple_tensorflow_tpu.ops.pallas.softmax_xent import (
    softmax_cross_entropy_reference)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype=dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        b, h, s, d = 2, 3, 64, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unaligned_seq_padding(self):
        b, h, s, d = 1, 2, 50, 16   # 50 not a multiple of block 32
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_lengths(self):
        b, h, sq, sk, d = 1, 2, 32, 96, 16
        q = rand(0, (b, h, sq, d))
        k = rand(1, (b, h, sk, d))
        v = rand(2, (b, h, sk, d))
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [8, 16])
    def test_gradients_match_reference(self, causal, d):
        b, h, s = 1, 2, 32
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=16, block_k=16)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_bf16(self):
        b, h, s, d = 1, 2, 64, 32
        q, k, v = (rand(i, (b, h, s, d), jnp.bfloat16) for i in range(3))
        out = flash_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32), atol=3e-2)

    @pytest.mark.parametrize("bias_shape", [(2, 64), (2, 1, 1, 64)])
    def test_padding_bias_matches_reference(self, bias_shape):
        b, h, s, d = 2, 3, 64, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        # mask out the tail 20 key positions of batch 0, 10 of batch 1
        mask = np.zeros((b, s), np.float32)
        mask[0, -20:] = -1e9
        mask[1, -10:] = -1e9
        bias = mask.reshape(bias_shape)
        out = flash_attention(q, k, v, bias=bias, block_q=32, block_k=32)
        ref = mha_reference(q, k, v, bias=mask)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bias_gradients_match_reference(self):
        b, h, s, d = 1, 2, 32, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        mask = np.zeros((b, s), np.float32)
        mask[0, -7:] = -1e9

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, bias=mask, block_q=16, block_k=16)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, bias=mask)))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_per_head_bias_rejected(self):
        b, h, s, d = 1, 2, 32, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        with pytest.raises(NotImplementedError):
            flash_attention(q, k, v, bias=np.zeros((b, h, s, s), np.float32))

    def test_dropout_deterministic_and_unbiased(self):
        b, h, s, d = 2, 4, 64, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        kwargs = dict(dropout_rate=0.4, dropout_seed=123,
                      block_q=32, block_k=32)
        o1 = flash_attention(q, k, v, **kwargs)
        o2 = flash_attention(q, k, v, **kwargs)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        o3 = flash_attention(q, k, v, dropout_rate=0.4, dropout_seed=999,
                             block_q=32, block_k=32)
        assert not np.allclose(np.asarray(o1), np.asarray(o3))
        # dropout zeroes ~rate of the prob mass: E[o] ~= no-dropout output.
        # With rate 0.4 and s=64 keys the per-element std is large, so only
        # check the batch-mean is in the right ballpark.
        o_ref = mha_reference(q, k, v)
        np.testing.assert_allclose(float(jnp.mean(o1)),
                                   float(jnp.mean(o_ref)), atol=0.05)

    def test_dropout_rate_zero_equals_no_dropout(self):
        b, h, s, d = 1, 2, 32, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        o0 = flash_attention(q, k, v, block_q=16, block_k=16)
        # rate exactly 0 skips the dropout plumbing even with a seed
        o1 = flash_attention(q, k, v, dropout_rate=0.0, dropout_seed=7,
                             block_q=16, block_k=16)
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))

    def test_dropout_gradients_match_finite_differences(self):
        # The dropout mask is a deterministic function of (seed, positions),
        # so flash(..., seed) is a fixed differentiable function and its
        # analytic vjp must match finite differences.
        b, h, s, d = 1, 1, 16, 8
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))

        def loss(q):
            o = flash_attention(q, k, v, dropout_rate=0.3, dropout_seed=42,
                                block_q=8, block_k=8)
            return jnp.sum(o * o)

        g = np.asarray(jax.grad(loss)(q))
        eps = 1e-3
        rng = np.random.RandomState(0)
        for _ in range(5):
            i = tuple(rng.randint(0, n) for n in q.shape)
            dq = np.zeros(q.shape, np.float32)
            dq[i] = eps
            fd = (float(loss(q + dq)) - float(loss(q - dq))) / (2 * eps)
            np.testing.assert_allclose(g[i], fd, atol=1e-2, rtol=1e-2)

    def test_dropout_with_causal_and_bias(self):
        b, h, s, d = 1, 2, 32, 16
        q, k, v = (rand(i, (b, h, s, d)) for i in range(3))
        mask = np.zeros((b, s), np.float32)
        mask[0, -5:] = -1e9
        o = flash_attention(q, k, v, bias=mask, causal=True,
                            dropout_rate=0.2, dropout_seed=5,
                            block_q=16, block_k=16)
        assert np.isfinite(np.asarray(o, np.float32)).all()
        # masked keys stay masked under dropout scaling: rows attending
        # only to live keys -> output finite; compare masked-average vs
        # reference loosely
        o2 = flash_attention(q, k, v, bias=mask, causal=True,
                             dropout_rate=0.2, dropout_seed=5,
                             block_q=16, block_k=16)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))


def _lse_reference(q, k, causal=False, bias=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / q.shape[-1] ** 0.5
    if bias is not None:
        s = s + jnp.asarray(bias)[:, None, None, :]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return jax.scipy.special.logsumexp(s, axis=-1)


# name -> (q_len, kv_len, heads, keywords of flash_attention, with a key
# bias, with an lse cotangent, the regime and tiles the call must take).
# In interpret mode the rule aligns to 8 rows, not to 128 lanes.
_REGIMES = {
    # one tile covers the head: all its heads in one step
    "single_s128": (128, 128, 2, {}, False, False,
                    ("single_pass", 128, 128, 2)),
    "single_s512_bias": (512, 512, 3, {}, True, False,
                         ("single_pass", 512, 512, 1)),
    "single_s512_causal": (512, 512, 1, dict(causal=True), False, False,
                           ("single_pass", 512, 512, 1)),
    "single_cross": (32, 96, 2, {}, True, False,
                     ("single_pass", 32, 96, 2)),
    # 50 keys pad to 56: the length mask must NOT be elided
    "single_pads": (50, 50, 2, {}, False, False,
                    ("single_pass", 56, 56, 2)),
    "single_pads_causal_bias": (50, 50, 2, dict(causal=True), True, False,
                                ("single_pass", 56, 56, 2)),
    # whole key range in one tile, several query blocks: the fused
    # backward accumulates dK and dV across them
    "single_q_blocks": (256, 96, 2, dict(block_q=64), True, False,
                        ("single_pass", 64, 96, 1)),
    "single_q_blocks_causal": (256, 256, 2, dict(block_q=64, causal=True),
                               False, False, ("single_pass", 64, 256, 1)),
    "single_lse": (128, 128, 2, dict(return_lse=True), True, True,
                   ("single_pass", 128, 128, 2)),
    # the rule's own streamed tiles
    "streamed_s2048": (2048, 2048, 1, {}, False, False,
                       ("streamed", 512, 512, 1)),
    # small tiles forced on a short sequence
    "streamed_causal": (256, 256, 2, dict(block_q=64, block_k=64,
                                          causal=True), False, False,
                        ("streamed", 64, 64, 1)),
    "streamed_pads_bias": (200, 200, 2, dict(block_q=64, block_k=64), True,
                           False, ("streamed", 64, 64, 1)),
    "streamed_cross": (64, 160, 2, dict(block_q=32, block_k=32), False,
                       False, ("streamed", 32, 32, 1)),
    "streamed_wide_keys": (128, 256, 2, dict(block_q=32, block_k=128), True,
                           False, ("streamed", 32, 128, 1)),
    "streamed_lse": (128, 128, 2, dict(block_q=32, block_k=64,
                                       return_lse=True, causal=True), False,
                     True, ("streamed", 32, 64, 1)),
}


class TestFlashAttentionRegimes:
    """Every regime the tile rule can produce, forward and all three
    gradients against the naive reference."""

    @pytest.mark.parametrize("name", list(_REGIMES))
    def test_matches_reference(self, name):
        sq, sk, h, kw, with_bias, with_lse, took = _REGIMES[name]
        kw = dict(kw)
        causal = kw.get("causal", False)
        d = 16
        q = rand(0, (1, h, sq, d))
        k, v = rand(1, (1, h, sk, d)), rand(2, (1, h, sk, d))
        bias = None
        if with_bias:
            bias = np.zeros((1, sk), np.float32)
            bias[0, -(sk // 5):] = -1e9
        w = rand(3, (1, h, sq))

        def flash(q, k, v):
            return flash_attention(q, k, v, bias=bias, **kw)

        def naive(q, k, v):
            o = mha_reference(q, k, v, causal=causal, bias=bias)
            return (o, _lse_reference(q, k, causal, bias)) if with_lse else o

        def run(attn):
            def loss(q, k, v):
                out = attn(q, k, v)
                o, lse = out if with_lse else (out, jnp.zeros_like(w))
                return jnp.sum(jnp.sin(o)) + jnp.sum(lse * w), (o, lse)
            return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)

        cell = kreg.metric_flash_tiles.get_cell(*map(str, took))
        before = cell.value()
        (_, (o1, lse1)), g1 = run(flash)
        assert cell.value() > before, (took, kreg.snapshot()["flash_tiles"])
        (_, (o2, lse2)), g2 = run(naive)
        np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(lse1, lse2, atol=2e-5, rtol=2e-5)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_dropout_mask_is_keyed_on_position_not_on_tiles(self, causal):
        # v = identity, so out[.., i, j] is the dropped probability of
        # key j itself: exactly 0 where the mask drops, > 0 where it
        # keeps. The zero pattern must be the same bit for bit whatever
        # the tiles (single pass with two heads a step; streamed) and in
        # the composed-XLA lowering; kept values agree to rounding.
        h, s = 2, 64
        q, k = rand(0, (1, h, s, s)), rand(1, (1, h, s, s))
        v = jnp.broadcast_to(jnp.eye(s), (1, h, s, s))
        kw = dict(causal=causal, dropout_rate=0.3, dropout_seed=11)
        outs = [np.asarray(flash_attention(q, k, v, **kw)),
                np.asarray(flash_attention(q, k, v, block_q=16, block_k=32,
                                           **kw)),
                np.asarray(flash_attention(q, k, v, block_q=32, **kw)),
                np.asarray(attention_xla(q, k, v, **kw))]
        live = np.tril(np.ones((s, s), bool)) if causal else np.ones(
            (s, s), bool)
        dropped = (outs[0] == 0.0) & live
        assert 0.2 < dropped.sum() / (h * live.sum()) < 0.4
        for o in outs[1:]:
            np.testing.assert_array_equal((o == 0.0) & live, dropped)
            np.testing.assert_allclose(o, outs[0], atol=2e-6, rtol=2e-5)

    def test_dropout_gradients_agree_between_regimes(self):
        h, s, d = 2, 64, 16
        q, k, v = (rand(i, (1, h, s, d)) for i in range(3))

        def grads(**kw):
            return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
                q, k, v, dropout_rate=0.3, dropout_seed=5, **kw))),
                (0, 1, 2))(q, k, v)

        for a, b_ in zip(grads(), grads(block_q=16, block_k=16)):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


# (q_len, kv_len, head_dim, dtype, causal, heads of a batch row) ->
# (block_q, block_k, heads a step), at the chip's 128-lane alignment
_TILE_TABLE = [
    ((512, 512, 64, jnp.bfloat16, False, 12), (512, 512, 3)),   # bert-base
    ((512, 512, 64, jnp.bfloat16, True, 16), (512, 512, 2)),    # lm-big
    ((128, 128, 64, jnp.bfloat16, False, 12), (128, 128, 12)),
    ((256, 256, 64, jnp.bfloat16, False, 12), (256, 256, 6)),
    ((500, 500, 64, jnp.bfloat16, False, 12), (512, 512, 3)),
    ((128, 512, 64, jnp.bfloat16, False, 12), (128, 512, 6)),   # cross
    ((1024, 1024, 64, jnp.bfloat16, False, 16), (512, 1024, 1)),
    ((2048, 2048, 64, jnp.bfloat16, True, 16), (512, 1024, 1)),
    ((8192, 8192, 64, jnp.bfloat16, False, 16), (512, 1024, 1)),
    ((512, 512, 128, jnp.bfloat16, False, 8), (512, 512, 2)),
    ((512, 512, 256, jnp.bfloat16, False, 8), (512, 512, 1)),
    ((512, 512, 64, jnp.float32, False, 12), (512, 512, 1)),
    ((1024, 1024, 64, jnp.float32, False, 12), (256, 1024, 1)),
    ((2048, 2048, 128, jnp.float32, False, 12), (512, 512, 1)),
    ((512, 512, 256, jnp.float32, False, 8), (256, 512, 1)),
    ((2048, 2048, 256, jnp.float32, False, 8), (256, 256, 1)),
]


class TestFlashAttentionTileRule:
    @pytest.mark.parametrize("shape,want", _TILE_TABLE,
                             ids=[str(i) for i in range(len(_TILE_TABLE))])
    def test_table(self, shape, want):
        assert tiles(*shape) == want

    @pytest.mark.parametrize("head_dim", [64, 128, 256])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_estimate_stays_under_the_budget(self, head_dim, dtype):
        for seq in (8, 128, 384, 512, 640, 1024, 2048, 8192):
            for kv in (seq, 128, 1536):
                for heads in (1, 12, 16):
                    bq, bk, g = tiles(seq, kv, head_dim, dtype, False, heads)
                    assert heads % g == 0
                    assert bq % 128 == 0 and bk % 128 == 0
                    for backward in (False, True):
                        assert vmem_bytes(bq, bk, head_dim, dtype, backward,
                                          g) <= VMEM_BUDGET, (seq, kv, heads)
                    # the whole key range in one tile, or tiles that
                    # cover it with under one alignment of padding each
                    assert bk >= kv or bk * -(-kv // bk) - kv < 128 * -(
                        -kv // bk)

    def test_causal_and_interpret_alignment(self):
        assert tiles(512, 512, 64, jnp.bfloat16, True, 12) == tiles(
            512, 512, 64, jnp.bfloat16, False, 12)
        assert tiles(50, 50, 16, jnp.float32, False, 2, align=8) == (
            56, 56, 2)


class TestLayerNorm:
    def test_forward(self):
        x = rand(0, (4, 6, 128))
        gamma = rand(1, (128,)) * 0.1 + 1.0
        beta = rand(2, (128,)) * 0.1
        out = layer_norm(x, gamma, beta, block_rows=8)
        ref = layer_norm_reference(x, gamma, beta)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_backward(self):
        x = rand(0, (16, 64))
        gamma = rand(1, (64,)) * 0.1 + 1.0
        beta = rand(2, (64,)) * 0.1

        def f(impl):
            def loss(x, g, b):
                return jnp.sum(jnp.tanh(impl(x, g, b)))
            return jax.grad(loss, argnums=(0, 1, 2))(x, gamma, beta)

        g1 = f(lambda x, g, b: layer_norm(x, g, b, block_rows=8))
        g2 = f(layer_norm_reference)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-4)

    def test_unaligned_rows(self):
        x = rand(0, (13, 32))   # 13 rows not a multiple of block 8
        gamma = jnp.ones((32,))
        beta = jnp.zeros((32,))
        out = layer_norm(x, gamma, beta, block_rows=8)
        ref = layer_norm_reference(x, gamma, beta)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_mixed_param_dtypes_backward(self):
        # cotangent dtypes must match each primal's dtype
        x = rand(0, (16, 64), jnp.bfloat16)
        gamma = jnp.ones((64,), jnp.bfloat16)
        beta = jnp.zeros((64,), jnp.float32)
        dx, dg, db = jax.grad(
            lambda x, g, b: jnp.sum(
                layer_norm(x, g, b, block_rows=8).astype(jnp.float32)),
            argnums=(0, 1, 2))(x, gamma, beta)
        assert dx.dtype == jnp.bfloat16
        assert dg.dtype == jnp.bfloat16
        assert db.dtype == jnp.float32


class TestSoftmaxXent:
    def test_forward(self):
        logits = rand(0, (24, 512)) * 3
        labels = jax.random.randint(jax.random.key(1), (24,), 0, 512)
        out = softmax_cross_entropy(logits, labels, block_rows=8)
        ref = softmax_cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_backward(self):
        logits = rand(0, (8, 128))
        labels = jax.random.randint(jax.random.key(1), (8,), 0, 128)

        g1 = jax.grad(lambda l: jnp.sum(
            softmax_cross_entropy(l, labels, block_rows=8)))(logits)
        g2 = jax.grad(lambda l: jnp.sum(
            softmax_cross_entropy_reference(l, labels)))(logits)
        np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-4)

    def test_batch_dims(self):
        logits = rand(0, (2, 5, 64))
        labels = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
        out = softmax_cross_entropy(logits, labels)
        assert out.shape == (2, 5)
        ref = softmax_cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_label_smoothing_fused(self):
        # fused smoothing == composed soft-target xent (fwd + grad),
        # across vocab blocks with a ragged edge
        logits = rand(0, (16, 300)) * 3
        labels = jax.random.randint(jax.random.key(1), (16,), 0, 300)
        sm = 0.1

        def composed(l):
            logp = jax.nn.log_softmax(l.astype(jnp.float32), axis=-1)
            conf, low = 1 - sm, sm / 299
            soft = jax.nn.one_hot(labels, 300) * (conf - low) + low
            return -jnp.sum(soft * logp, -1)

        out = softmax_cross_entropy(logits, labels, label_smoothing=sm,
                                    block_rows=8, block_vocab=128)
        np.testing.assert_allclose(out, composed(logits), atol=1e-5,
                                   rtol=1e-5)
        g1 = jax.grad(lambda l: jnp.sum(softmax_cross_entropy(
            l, labels, label_smoothing=sm, block_rows=8,
            block_vocab=128)))(logits)
        g2 = jax.grad(lambda l: jnp.sum(composed(l)))(logits)
        np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-4)

    def test_vocab_blocking_ragged_edge(self):
        # vocab spanning several blocks with a ragged final block (the
        # streamed online-softmax path, unpadded); fwd + bwd vs reference
        logits = rand(0, (16, 700)) * 3
        labels = jax.random.randint(jax.random.key(1), (16,), 0, 700)
        out = softmax_cross_entropy(logits, labels, block_rows=8,
                                    block_vocab=256)
        ref = softmax_cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        g1 = jax.grad(lambda l: jnp.sum(softmax_cross_entropy(
            l, labels, block_rows=8, block_vocab=256)))(logits)
        g2 = jax.grad(lambda l: jnp.sum(
            softmax_cross_entropy_reference(l, labels)))(logits)
        np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-4)


class TestQuantMatmul:
    def test_matches_reference_quantization(self):
        x = rand(0, (48, 64))
        w = rand(1, (64, 96))
        wq, ws = quantize_colwise(w)
        out = quant_matmul(x, wq, ws)
        ref = quant_matmul_reference(x, wq, ws)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    def test_straight_through_gradient(self):
        from simple_tensorflow_tpu.ops.pallas import quant_matmul_ste

        x = rand(0, (16, 32))
        w = rand(1, (32, 24))
        wq, ws = quantize_colwise(w)
        c = rand(2, (16, 24))   # fixed cotangent weighting (linear loss)
        dx = jax.grad(lambda x: jnp.sum(
            quant_matmul_ste(x, wq, ws) * c))(x)
        # STE: dx must equal the dense-matmul gradient under the same
        # cotangent (quantization rounding contributes no derivative)
        wd = wq.astype(jnp.float32) * ws[None, :]
        dx_ref = jax.grad(lambda x: jnp.sum((x @ wd) * c))(x)
        np.testing.assert_allclose(dx, dx_ref, atol=1e-5, rtol=1e-5)

    def test_scale_gradient(self):
        from simple_tensorflow_tpu.ops.pallas import quant_matmul_ste
        from simple_tensorflow_tpu.ops.pallas.quant_matmul import (
            quantize_rowwise)

        x = rand(0, (16, 32))
        w = rand(1, (32, 24))
        wq, ws = quantize_colwise(w)
        c = rand(2, (16, 24))
        d_ws = jax.grad(lambda s: jnp.sum(
            quant_matmul_ste(x, wq, s) * c))(ws)
        # y = (xq@wq) * x_scale ⊗ w_scale — analytic d/dw_scale
        xq, x_scale = quantize_rowwise(x)
        acc = (xq.astype(jnp.int32) @ wq.astype(jnp.int32)).astype(
            jnp.float32)
        ref = jnp.sum(c * acc * x_scale[:, None], axis=0)
        np.testing.assert_allclose(d_ws, ref, atol=1e-4, rtol=1e-4)

    def test_close_to_float_matmul(self):
        x = rand(0, (32, 128))
        w = rand(1, (128, 64))
        wq, ws = quantize_colwise(w)
        out = quant_matmul(x, wq, ws)
        ref = x @ w
        # int8 dynamic quantization error budget
        err = jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9)
        assert err < 0.05, float(err)


class TestGraphOps:
    def test_flash_attention_graph_op(self):
        import simple_tensorflow_tpu as stf

        stf.reset_default_graph()
        arrays = [np.asarray(rand(i, (1, 2, 32, 16))) for i in range(3)]
        out_t = stf.nn.fused_attention(*(stf.constant(a) for a in arrays),
                                       causal=True)
        sess = stf.Session()
        out = sess.run(out_t)
        ref = mha_reference(*arrays, causal=True)
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5)

    def test_fused_ops_registered_on_package_import(self):
        import simple_tensorflow_tpu  # noqa: F401
        from simple_tensorflow_tpu.framework import op_registry

        for op_type in ("FlashAttention", "FusedLayerNorm",
                        "FusedSoftmaxXent", "QuantMatMul"):
            assert op_registry.is_registered(op_type), op_type


class TestLayerNormWideFeatures:
    def test_block_rows_shrink_for_wide_features(self):
        # (block_rows, n) f32 tiles must stay inside the VMEM budget: at
        # n=8192 the default 256-row block would be an 8 MB tile; the
        # wrapper shrinks rows and the result still matches the reference
        from simple_tensorflow_tpu.ops.pallas.layer_norm import (
            layer_norm, layer_norm_reference)

        # rows must exceed the shrunk block (4MB/8192/4 = 128) so the test
        # actually exercises the clamp: at 512 rows the old code would run
        # a 256-row / 8 MB tile, the clamp runs 128-row / 4 MB tiles
        x = rand(0, (512, 8192)).astype(jnp.bfloat16)
        g = jnp.ones((8192,), jnp.float32)
        b = jnp.zeros((8192,), jnp.float32)
        o1 = layer_norm(x, g, b)
        o2 = layer_norm_reference(x, g, b)
        np.testing.assert_allclose(o1.astype(jnp.float32),
                                   o2.astype(jnp.float32), atol=1e-2)
        gr = jax.grad(lambda x: jnp.sum(layer_norm(x, g, b)
                                        .astype(jnp.float32)))(x)
        assert gr.shape == x.shape


class TestQuantMatmulKBlocking:
    def test_multi_k_block_with_ragged_k(self):
        # contraction longer than TILE_K and NOT a multiple of it: the
        # streamed k-blocks must pad (a ragged final block accumulated
        # out-of-bounds garbage before the fix)
        from simple_tensorflow_tpu.ops.pallas.quant_matmul import TILE_K

        x = rand(0, (32, 2 * TILE_K + 64), jnp.bfloat16)
        w = rand(1, (2 * TILE_K + 64, 96))
        wq, s = quantize_colwise(w)
        o1 = quant_matmul(x, wq, s)
        o2 = quant_matmul_reference(x, wq, s)
        np.testing.assert_allclose(o1.astype(jnp.float32),
                                   o2.astype(jnp.float32), atol=1e-4,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# ISSUE 11 satellites: backward-pass parity through the GRAPH path
# (stf.gradients -> SymbolicGradient -> the op's routed lowering ->
# custom VJP) against jax.grad of the XLA reference, plus odd/non-pow2
# shape coverage for all four kernels. Interpret mode on the CPU test
# mesh; shapes kept tiny so tier-1 wall time stays bounded.
# ---------------------------------------------------------------------------


class TestGraphBackwardParity:
    """Gradient parity of every routed kernel vs its XLA reference,
    exercised through stf.gradients on a live graph with the registry
    pinned to `force` (Pallas, interpret mode)."""

    @pytest.fixture(autouse=True)
    def _force_mode(self):
        import simple_tensorflow_tpu as stf

        stf.kernels.set_mode("force")
        stf.reset_default_graph()
        yield
        stf.kernels.set_mode(None)
        stf.kernels.clear_decisions()
        stf.reset_default_graph()

    def _session_grads(self, loss_t, xs):
        import simple_tensorflow_tpu as stf

        grads = stf.gradients(loss_t, xs)
        with stf.Session() as sess:
            return [np.asarray(g) for g in sess.run(grads)]

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_attention_graph_grads(self, causal):
        import simple_tensorflow_tpu as stf

        b, h, s, d = 1, 2, 37, 12    # odd seq, non-pow2 head_dim
        arrays = [np.asarray(rand(i, (b, h, s, d))) for i in range(3)]
        ts = [stf.constant(a) for a in arrays]
        out = stf.nn.fused_attention(*ts, causal=causal)
        loss = stf.reduce_sum(stf.sin(out))
        got = self._session_grads(loss, ts)

        def ref(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

        want = jax.grad(ref, argnums=(0, 1, 2))(*arrays)
        for g1, g2 in zip(got, want):
            np.testing.assert_allclose(g1, np.asarray(g2), atol=2e-4,
                                       rtol=2e-4)

    def test_layer_norm_graph_grads(self):
        import simple_tensorflow_tpu as stf

        x = np.asarray(rand(0, (13, 45)))          # both dims odd
        gamma = np.asarray(rand(1, (45,))) * 0.1 + 1.0
        beta = np.asarray(rand(2, (45,))) * 0.1
        ts = [stf.constant(a) for a in (x, gamma, beta)]
        out = stf.nn.fused_layer_norm(*ts)
        loss = stf.reduce_sum(stf.tanh(out))
        got = self._session_grads(loss, ts)

        def ref(x, g, b):
            return jnp.sum(jnp.tanh(layer_norm_reference(x, g, b)))

        want = jax.grad(ref, argnums=(0, 1, 2))(x, gamma, beta)
        for g1, g2 in zip(got, want):
            np.testing.assert_allclose(g1, np.asarray(g2), atol=1e-4,
                                       rtol=1e-3)

    def test_softmax_xent_graph_grads(self):
        import simple_tensorflow_tpu as stf

        logits = np.asarray(rand(0, (9, 301))) * 3  # ragged vocab block
        labels = np.asarray(jax.random.randint(
            jax.random.key(1), (9,), 0, 301), np.int32)
        lt = stf.constant(logits)
        out = stf.nn.fused_softmax_cross_entropy(
            lt, stf.constant(labels), label_smoothing=0.1)
        loss = stf.reduce_sum(out)
        (got,) = self._session_grads(loss, [lt])

        def ref(l):
            return jnp.sum(softmax_cross_entropy_reference(
                l, labels, label_smoothing=0.1))

        want = jax.grad(ref)(logits)
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-4,
                                   rtol=1e-3)

    def test_quant_matmul_graph_grads(self):
        import simple_tensorflow_tpu as stf

        x = np.asarray(rand(0, (17, 33)))           # odd m/k/n
        w = np.asarray(rand(1, (33, 29)))
        wq, ws = quantize_colwise(w)
        xt = stf.constant(x)
        st = stf.constant(np.asarray(ws))
        out = stf.nn.quantized_matmul(xt, stf.constant(np.asarray(wq)), st)
        c = np.asarray(rand(2, (17, 29)))
        loss = stf.reduce_sum(out * stf.constant(c))
        got = self._session_grads(loss, [xt, st])
        from simple_tensorflow_tpu.ops.pallas.quant_matmul import (
            quant_matmul_ste_reference)

        def ref(x, s):
            return jnp.sum(quant_matmul_ste_reference(
                x, np.asarray(wq), s) * c)

        want = jax.grad(ref, argnums=(0, 1))(x, np.asarray(ws))
        for g1, g2 in zip(got, want):
            np.testing.assert_allclose(g1, np.asarray(g2), atol=2e-4,
                                       rtol=2e-4)


class TestOddShapeForward:
    """Non-pow2 / odd shape sweep for all four kernels (jax level,
    interpret mode): the padding/masking paths on ragged edges."""

    @pytest.mark.parametrize("shape", [(1, 1, 7, 4), (2, 3, 33, 24),
                                       (1, 2, 65, 12)])
    def test_flash_attention_odd(self, shape):
        b, h, s, d = shape
        q, k, v = (rand(i, shape) for i in range(3))
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("rows,n", [(1, 3), (7, 129), (29, 255)])
    def test_layer_norm_odd(self, rows, n):
        x = rand(0, (rows, n))
        g = rand(1, (n,)) * 0.1 + 1.0
        b = rand(2, (n,)) * 0.1
        out = layer_norm(x, g, b, block_rows=8)
        ref = layer_norm_reference(x, g, b)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)

    @pytest.mark.parametrize("rows,vocab", [(1, 5), (11, 257), (5, 1023)])
    def test_softmax_xent_odd(self, rows, vocab):
        logits = rand(0, (rows, vocab)) * 2
        labels = jax.random.randint(jax.random.key(1), (rows,), 0, vocab)
        out = softmax_cross_entropy(logits, labels, block_rows=8,
                                    block_vocab=128)
        ref = softmax_cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)

    @pytest.mark.parametrize("m,k,n", [(1, 3, 5), (17, 65, 33),
                                       (31, 129, 7)])
    def test_quant_matmul_odd(self, m, k, n):
        x = rand(0, (m, k))
        w = rand(1, (k, n))
        wq, ws = quantize_colwise(w)
        out = quant_matmul(x, wq, ws)
        ref = quant_matmul_reference(x, wq, ws)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)
