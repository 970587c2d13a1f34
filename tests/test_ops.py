"""Kernel tests: chunked attention and the Pallas flash kernel (interpret mode
on CPU; the same code compiles natively on TPU) against a dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import chunked_attention, flash_attention


def dense_reference(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    if causal:
        t = q.shape[2]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def make_qkv(b=2, h=2, t=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d), dtype) for k in ks)


class TestChunkedAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        out = chunked_attention(q, k, v, causal=causal, block_size=64)
        ref = dense_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_dense(self):
        q, k, v = make_qkv(t=128, d=32)

        def loss_chunked(q, k, v):
            return chunked_attention(q, k, v, causal=True, block_size=32).sum()

        def loss_dense(q, k, v):
            return dense_reference(q, k, v, True).sum()

        g1 = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
        ref = dense_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_dense(self, causal):
        q, k, v = make_qkv(t=128, d=32, seed=3)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal,
                                  block_q=32, block_kv=32)
            return (out * out).sum()

        def loss_dense(q, k, v):
            out = dense_reference(q, k, v, causal)
            return (out * out).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)

    def test_bfloat16_inputs(self):
        q, k, v = make_qkv(dtype=jnp.bfloat16, seed=5)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
        assert out.dtype == jnp.bfloat16
        ref = dense_reference(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=0.05, rtol=0.05
        )

    def test_rejects_misaligned_seq(self):
        q, k, v = make_qkv(t=100)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, k, v, block_q=64, block_kv=64)

    def test_jit_compose(self):
        q, k, v = make_qkv(t=128, d=32)
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_kv=32
        ))(q, k, v)
        assert out.shape == q.shape


def dense_masked_reference(q, k, v, kv_mask, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    s = jnp.where(kv_mask[:, None, None, :], s, -1e30)
    if causal:
        t = q.shape[2]
        s = jnp.where(np.tril(np.ones((t, t), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


class TestFlashKvMask:
    """Padding-mask support (encoder models): the mask rides into the
    kernels as a KV bias; forward and all gradients must match a dense
    masked softmax."""

    def make_mask(self, b, t, valid):
        mask = np.zeros((b, t), bool)
        for i, n in enumerate(valid):
            mask[i, :n] = True
        return jnp.asarray(mask)

    def test_matches_dense_masked(self):
        q, k, v = make_qkv(b=3, h=2, t=256, d=64)
        kv_mask = self.make_mask(3, 256, [256, 200, 128])
        out = flash_attention(q, k, v, causal=False, kv_mask=kv_mask)
        ref = dense_masked_reference(q, k, v, kv_mask)
        # padded QUERY rows attend over valid keys in both impls; compare all
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_dense_masked(self):
        q, k, v = make_qkv(b=2, h=2, t=128, d=32, seed=3)
        kv_mask = self.make_mask(2, 128, [128, 96])

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=False, kv_mask=kv_mask)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = dense_masked_reference(q, k, v, kv_mask)
            return jnp.sum(o * o)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_causal_plus_mask(self):
        q, k, v = make_qkv(b=2, h=2, t=256, d=32, seed=5)
        kv_mask = self.make_mask(2, 256, [256, 160])
        out = flash_attention(q, k, v, causal=True, kv_mask=kv_mask)
        ref = dense_masked_reference(q, k, v, kv_mask, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_fully_masked_batch_row_is_zero(self):
        q, k, v = make_qkv(b=2, h=1, t=128, d=32)
        kv_mask = self.make_mask(2, 128, [128, 0])   # row 1: nothing to attend

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=False,
                                           kv_mask=kv_mask))

        out = flash_attention(q, k, v, causal=False, kv_mask=kv_mask)
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_allclose(np.asarray(out[1]), 0.0, atol=1e-6)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))
            np.testing.assert_allclose(np.asarray(g[1]), 0.0, atol=1e-6)

    def test_bad_mask_shape_rejected(self):
        q, k, v = make_qkv(b=2, h=1, t=128, d=32)
        with pytest.raises(ValueError, match="kv_mask shape"):
            flash_attention(q, k, v, kv_mask=jnp.ones((2, 64), bool))


class TestBertFlashPath:
    def test_bert_flash_matches_naive(self):
        import dataclasses

        from lzy_tpu.models.bert import BertConfig, BertMlm

        cfg = BertConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=2,
                         d_ff=128, max_seq_len=128, dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, 512)
        attn_mask = jnp.asarray(
            np.arange(128)[None, :] < np.array([[128], [80]])
        )
        model = BertMlm(cfg)
        params = model.init(jax.random.PRNGKey(1), tokens, attn_mask)
        naive = model.apply(params, tokens, attn_mask)
        flash_cfg = dataclasses.replace(cfg, use_flash_kernel=True)
        flash = BertMlm(flash_cfg).apply(params, tokens, attn_mask)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(naive),
                                   atol=2e-4, rtol=2e-4)


class TestChunkedCrossEntropy:
    """ops/chunked_ce.py must match the dense logits path exactly — value AND
    gradients — while never materializing [N, V]."""

    def _setup(self, n=12, d=16, v=64, seed=0):
        import numpy as np

        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        head = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
        return x, head, labels

    def _dense(self, x, head, labels, mask=None):
        from lzy_tpu.models.common import cross_entropy_loss

        logits = jnp.einsum("nd,vd->nv", x, head,
                            preferred_element_type=jnp.float32)
        return cross_entropy_loss(logits, labels, mask)

    def test_forward_matches_dense(self):
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        fused = chunked_cross_entropy(x, head, labels, chunk=16)
        assert jnp.allclose(fused, self._dense(x, head, labels), atol=1e-5)

    def test_gradients_match_dense(self):
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        gx_f, gh_f = jax.grad(
            lambda a, h: chunked_cross_entropy(a, h, labels, chunk=16),
            argnums=(0, 1))(x, head)
        gx_d, gh_d = jax.grad(
            lambda a, h: self._dense(a, h, labels), argnums=(0, 1))(x, head)
        assert jnp.allclose(gx_f, gx_d, atol=1e-5)
        assert jnp.allclose(gh_f, gh_d, atol=1e-5)

    def test_mask_weighting_matches(self):
        import numpy as np

        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        mask = jnp.asarray(
            np.random.default_rng(1).integers(0, 2, size=labels.shape),
            jnp.float32)
        fused = chunked_cross_entropy(x, head, labels, chunk=16, mask=mask)
        dense = self._dense(x, head, labels, mask)
        assert jnp.allclose(fused, dense, atol=1e-5)
        gx_f = jax.grad(lambda a: chunked_cross_entropy(
            a, head, labels, chunk=16, mask=mask))(x)
        gx_d = jax.grad(lambda a: self._dense(a, head, labels, mask))(x)
        assert jnp.allclose(gx_f, gx_d, atol=1e-5)

    def test_batched_and_indivisible_chunk(self):
        """Rows that do not fill the last block (12 tokens in blocks of 5,
        batched input, a vocabulary no power of two): value and gradients."""
        import numpy as np

        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((2, 6, 16)), jnp.float32)
        head = jnp.asarray(rng.standard_normal((60, 16)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 60, size=(2, 6)), jnp.int32)
        fused, (gx_f, gh_f) = jax.value_and_grad(
            lambda a, h: chunked_cross_entropy(a, h, labels, chunk=5),
            argnums=(0, 1))(x, head)
        dense, (gx_d, gh_d) = jax.value_and_grad(
            lambda a, h: self._dense(a.reshape(12, 16), h,
                                     labels.reshape(12)),
            argnums=(0, 1))(x, head)
        assert jnp.allclose(fused, dense, atol=1e-5)
        assert jnp.allclose(gx_f, gx_d, atol=1e-5)
        assert jnp.allclose(gh_f, gh_d, atol=1e-5)

    @pytest.mark.parametrize("chunk", [1, 4, 5, 100, None],
                             ids=["one", "divisor", "non_divisor",
                                  "past_the_tokens", "from_shapes"])
    def test_any_block_size_gives_the_dense_numbers(self, chunk):
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        fused, grads = jax.value_and_grad(
            lambda a, h: chunked_cross_entropy(a, h, labels, chunk=chunk),
            argnums=(0, 1))(x, head)
        dense, want = jax.value_and_grad(
            lambda a, h: self._dense(a, h, labels), argnums=(0, 1))(x, head)
        assert jnp.allclose(fused, dense, atol=1e-5)
        for got, ref in zip(grads, want):
            assert jnp.allclose(got, ref, atol=1e-5)

    @pytest.mark.parametrize("wrt", [0, 1], ids=["features", "head"])
    def test_a_cotangent_that_is_not_one_scales_both_gradients(self, wrt):
        # the gradients are made in the forward for a cotangent of 1: the
        # backward has to multiply them by what arrives
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        got = jax.grad(lambda a, h: 3.0 * chunked_cross_entropy(
            a, h, labels, chunk=16), argnums=wrt)(x, head)
        want = jax.grad(lambda a, h: 3.0 * self._dense(a, h, labels),
                        argnums=wrt)(x, head)
        assert jnp.allclose(got, want, atol=1e-5)

    @pytest.mark.parametrize("wrt", [None, 0, 1],
                             ids=["loss", "features", "head"])
    def test_bf16_operands_match_the_dense_bf16_path(self, wrt):
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup(n=24, d=32, v=128)
        x, head = x.astype(jnp.bfloat16), head.astype(jnp.bfloat16)
        if wrt is None:
            got = chunked_cross_entropy(x, head, labels, chunk=16)
            assert got.dtype == jnp.float32
            assert jnp.allclose(got, self._dense(x, head, labels), atol=1e-5)
            return
        got = jax.grad(lambda a, h: chunked_cross_entropy(
            a, h, labels, chunk=16), argnums=wrt)(x, head)
        want = jax.grad(lambda a, h: self._dense(a, h, labels),
                        argnums=wrt)(x, head)
        assert got.dtype == jnp.bfloat16
        # one bf16 rounding of dlogits and one of the result, each 2**-8
        assert jnp.allclose(got.astype(jnp.float32),
                            want.astype(jnp.float32), rtol=2e-2, atol=2e-4)

    @pytest.mark.parametrize("wrt", [None, 0, 1],
                             ids=["loss", "features", "head"])
    def test_a_mask_of_zeros_gives_zero_and_nothing_nan(self, wrt):
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        x, head, labels = self._setup()
        mask = jnp.zeros(labels.shape, jnp.float32)
        if wrt is None:
            assert float(chunked_cross_entropy(
                x, head, labels, chunk=5, mask=mask)) == 0.0
            return
        got = jax.grad(lambda a, h: chunked_cross_entropy(
            a, h, labels, chunk=5, mask=mask), argnums=wrt)(x, head)
        assert not jnp.any(jnp.isnan(got))
        assert float(jnp.abs(got).max()) == 0.0

    def test_last_position_as_a_zero_weight_row_equals_the_slice(self):
        """``_lm_loss`` hands the features whole, the labels shifted and the
        last column's weight 0, where it used to hand ``features[:, :-1]``:
        the same loss and the same gradient of every parameter."""
        import dataclasses

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(vocab_size=128), fused_ce=True,
            dtype=jnp.float32, param_dtype=jnp.float32)
        params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
        mask = jax.random.bernoulli(jax.random.PRNGKey(2), 0.7, (2, 16))
        model = llama.Llama(cfg)

        def sliced(p):
            features, head = model.apply({"params": p}, tokens)
            return chunked_cross_entropy(features[:, :-1], head,
                                         tokens[:, 1:], mask=mask[:, 1:])

        want_loss, want = jax.value_and_grad(sliced)(params)
        loss, grads = jax.value_and_grad(llama.make_loss_fn(cfg))(
            params, {"tokens": tokens, "mask": mask})
        assert abs(float(loss) - float(want_loss)) < 1e-6
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                                jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-6, rtol=1e-5,
                err_msg=jax.tree_util.keystr(path))

    def test_fused_llama_loss_matches_dense(self):
        import dataclasses

        from lzy_tpu.models import llama, unbox

        cfg = llama.LlamaConfig.tiny(vocab_size=128)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
        dense_loss = llama.make_loss_fn(cfg)(params, {"tokens": tokens})
        fused_cfg = dataclasses.replace(cfg, fused_ce=True)
        fused_loss = llama.make_loss_fn(fused_cfg)(params, {"tokens": tokens})
        assert jnp.allclose(dense_loss, fused_loss, atol=1e-4)

    def test_generate_works_with_fused_ce_config(self):
        import dataclasses

        from lzy_tpu.models import generate, llama, unbox

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                                  fused_ce=True)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        prompt = jnp.array([[5, 7, 9]], jnp.int32)
        out = generate(cfg, params, prompt, max_new_tokens=4,
                       temperature=0.0)
        assert out.shape[1] == prompt.shape[1] + 4


class TestSegmentedAttention:
    """Packed-document masking: attention confined to equal segment ids, in
    both the Pallas kernel (with its data-dependent block skipping) and the
    chunked fallback, forward and backward."""

    @staticmethod
    def dense_segmented(q, k, v, seg, causal):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (d ** -0.5)
        keep = seg[:, None, :, None] == seg[:, None, None, :]
        if causal:
            t = q.shape[2]
            keep = keep & np.tril(np.ones((t, t), bool))[None, None]
        s = jnp.where(keep, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))

    @staticmethod
    def packed_segments(b=2, t=256, seed=1):
        """Non-decreasing ids with uneven document lengths per row."""
        rng = np.random.default_rng(seed)
        out = np.zeros((b, t), np.int32)
        for i in range(b):
            cuts = np.sort(rng.choice(np.arange(1, t), size=3, replace=False))
            out[i] = np.searchsorted(cuts, np.arange(t), side="right")
        return jnp.asarray(out)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_matches_dense(self, causal):
        q, k, v = make_qkv()
        seg = self.packed_segments()
        out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=128, block_kv=128)
        ref = self.dense_segmented(q, k, v, seg, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_chunked_matches_dense(self, causal):
        q, k, v = make_qkv()
        seg = self.packed_segments()
        out = chunked_attention(q, k, v, causal=causal, segment_ids=seg,
                                block_size=64)
        ref = self.dense_segmented(q, k, v, seg, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_flash_gradients_match_dense(self):
        q, k, v = make_qkv(t=256, d=32)
        seg = self.packed_segments()

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, segment_ids=seg,
                                   block_q=128, block_kv=128).sum()

        def loss_dense(q, k, v):
            return self.dense_segmented(q, k, v, seg, True).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_segments_plus_kv_mask_compose(self):
        q, k, v = make_qkv()
        seg = self.packed_segments()
        mask = jnp.ones(seg.shape, bool).at[:, -64:].set(False)
        out = flash_attention(q, k, v, causal=False, segment_ids=seg,
                              kv_mask=mask)
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (d ** -0.5)
        keep = (seg[:, None, :, None] == seg[:, None, None, :]) \
            & mask[:, None, None, :]
        ref = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(jnp.where(keep, s, -1e30), -1),
                         v.astype(jnp.float32))
        # flash semantics: a query whose whole document is masked out gets
        # zero output (naive softmax would give a uniform average instead)
        ref = jnp.where(keep.any(-1)[..., None], ref, 0.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_segment_equals_plain(self):
        q, k, v = make_qkv()
        seg = jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)
        out = flash_attention(q, k, v, causal=True, segment_ids=seg)
        ref = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)

    def test_bad_segment_shape_rejected(self):
        q, k, v = make_qkv()
        with pytest.raises(ValueError, match="segment_ids"):
            flash_attention(q, k, v, segment_ids=jnp.zeros((2, 8), jnp.int32))

    @pytest.mark.parametrize("causal", [False, True])
    def test_repeated_id_in_nonadjacent_runs_is_a_new_document(self, causal):
        """Documents are contiguous RUNS: reusing an id later must start a
        new document, identically in the flash kernel (whose block skipping
        is run-based) and the chunked fallback."""
        q, k, v = make_qkv()
        seg = jnp.asarray(
            np.concatenate([np.zeros(64), np.ones(64), np.zeros(128)])
            .astype(np.int32)[None, :].repeat(2, 0)
        )
        out_flash = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                                    block_q=128, block_kv=128)
        out_chunk = chunked_attention(q, k, v, causal=causal,
                                      segment_ids=seg, block_size=64)
        # run-normalized ids = what both paths must behave like
        runs = jnp.asarray(
            np.concatenate([np.zeros(64), np.ones(64), 2 * np.ones(128)])
            .astype(np.int32)[None, :].repeat(2, 0)
        )
        ref = self.dense_segmented(q, k, v, runs, causal)
        np.testing.assert_allclose(np.asarray(out_flash), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(out_chunk), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# -- the flash kernels, by what their code branches on -------------------------


def _segments(t, cuts, b=1):
    """[b, t] ids: a new document starts at each of ``cuts``."""
    ids = np.searchsorted(np.asarray(cuts), np.arange(t), side="right")
    return np.broadcast_to(ids.astype(np.int32), (b, t))


def _dense_keep(t, causal, seg, kv_mask):
    """[b, t, t] bool: which (query, key) the dense reference keeps."""
    b = 1 if seg is None else seg.shape[0]
    keep = np.ones((b, t, t), bool)
    if causal:
        keep &= np.tril(np.ones((t, t), bool))
    if seg is not None:
        runs = np.cumsum(np.concatenate(
            [np.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], 1), 1)
        keep &= runs[:, :, None] == runs[:, None, :]
    if kv_mask is not None:
        keep = keep & np.asarray(kv_mask)[:, None, :]
    return keep


def _dense_attention(q, k, v, keep):
    """float32 softmax attention over ``keep``; a query that keeps no key
    gives zero (and takes no gradient)."""
    keep = jnp.asarray(keep)[:, None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return jnp.where(keep.any(-1)[..., None], out, 0.0)


#: name -> (causal, document cuts or None, kv_mask's dropped keys or None,
#: block_q, block_kv), all at t = 512: 4 x 4 pairs of 128, or rectangles
_T = 512
FLASH_CASES = {
    # a document edge inside a block: edge pairs on both sides of it
    "edge_inside_a_block": (True, [200, 330], None, 128, 128),
    # a document that spans several blocks: pairs that keep every element
    "document_spans_blocks": (True, [400], None, 128, 128),
    "edges_on_block_boundaries": (True, [128, 384], None, 128, 128),
    "one_document_a_row": (True, [], None, 128, 128),
    "causal_no_segments": (True, None, None, 128, 128),
    "no_mask_at_all": (False, None, None, 128, 128),
    "bidirectional_segments": (False, [200, 330], None, 128, 128),
    "bidirectional_document_spans_blocks": (False, [60, 460], None,
                                            128, 128),
    # keys 0-2 dropped under a causal mask: queries 0-2 see nothing
    "kv_mask_fully_masked_rows": (True, None, [0, 1, 2], 128, 128),
    "kv_mask_bidirectional": (False, None, list(range(300, 512)), 128, 128),
    "kv_mask_with_segments": (False, [200, 330], list(range(200, 330)),
                              128, 128),
    "wide_queries": (True, [100, 400], None, 256, 128),
    "wide_keys": (True, [100, 400], None, 128, 256),
    "wide_keys_bidirectional": (False, [100, 400], None, 128, 256),
}


class TestFlashBranches:
    """Forward and all three gradients against the dense float32 reference,
    over everything the kernels branch on: where the documents' edges fall
    against the blocks (inside one, on their boundaries, blocks apart), the
    causal flag, a ``kv_mask`` bias, rectangular blocks, the input dtype."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("case", sorted(FLASH_CASES))
    def test_forward_and_gradients_match_dense(self, case, dtype):
        causal, cuts, dropped, block_q, block_kv = FLASH_CASES[case]
        q, k, v = make_qkv(b=1, h=2, t=_T, d=64, dtype=dtype, seed=3)
        do = jax.random.normal(jax.random.PRNGKey(9), q.shape, dtype)
        seg = None if cuts is None else _segments(_T, cuts)
        kv_mask = None
        if dropped is not None:
            kv_mask = np.ones((1, _T), bool)
            kv_mask[:, dropped] = False
        keep = _dense_keep(_T, causal, seg, kv_mask)

        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
                segment_ids=None if seg is None else jnp.asarray(seg),
                kv_mask=None if kv_mask is None else jnp.asarray(kv_mask)),
            q, k, v)
        ref, ref_vjp = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, keep), q, k, v)
        assert out.dtype == dtype
        # bfloat16: p and ds are rounded to 8 bits before their products,
        # as the outputs are
        tol = dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 \
            else dict(atol=4e-2, rtol=4e-2)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), **tol)
        tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 \
            else dict(atol=8e-2, rtol=4e-2)
        got = vjp(do)
        want = ref_vjp(do.astype(jnp.float32))
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"d{name}", **tol)
        if case == "kv_mask_fully_masked_rows":
            assert not np.asarray(out[:, :, :3], np.float32).any()
            assert not np.asarray(got[0][:, :, :3], np.float32).any()

    def test_products_take_their_operands_as_loaded(self):
        """Every product of the three kernels multiplies operands of the
        input dtype (bfloat16 in, bfloat16 on the matrix unit) and
        accumulates in float32: nothing is widened before it is multiplied,
        and ``p`` / ``ds`` are cast down once, where the parent's float32
        product rounded them inside."""
        from jax.extend import core as jex_core

        q, k, v = make_qkv(b=1, h=2, t=256, d=64, dtype=jnp.bfloat16)
        seg = jnp.asarray(_segments(256, [100]))

        def fwd_bwd(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, segment_ids=seg, block_q=128,
                block_kv=128), q, k, v)
            return vjp(out)

        def walk(jaxpr, inside_kernel, found):
            for eqn in jaxpr.eqns:
                kernel = inside_kernel or eqn.primitive.name == "pallas_call"
                if eqn.primitive.name == "dot_general" and inside_kernel:
                    found.append(([x.aval.dtype for x in eqn.invars],
                                  eqn.outvars[0].aval.dtype))
                for param in eqn.params.values():
                    for sub in (param if isinstance(param, (list, tuple))
                                else [param]):
                        if isinstance(sub, jex_core.ClosedJaxpr):
                            walk(sub.jaxpr, kernel, found)
                        elif isinstance(sub, jex_core.Jaxpr):
                            walk(sub, kernel, found)
            return found

        products = walk(jax.make_jaxpr(fwd_bwd)(q, k, v).jaxpr, False, [])
        # 2 forward, 3 dQ, 4 dK/dV
        assert len(products) == 9, len(products)
        for operands, result in products:
            assert operands == [jnp.bfloat16, jnp.bfloat16], operands
            assert result == jnp.float32


class TestBlockPairCensus:
    """``block_pair_census`` and the loop bounds it shares with the kernels
    (``_pair_ranges``) against brute force over the dense mask: a pair is
    needed when the mask keeps anything of it, and the kernels visit exactly
    the needed pairs, from either side."""

    @staticmethod
    def _cases():
        rng = np.random.default_rng(5)
        t = 1024
        out = [("one_document", np.zeros((2, t), np.int32)),
               ("on_boundaries", _segments(t, [256, 512, 768], b=2))]
        for n in (1, 3, 9):
            rows = [_segments(t, np.sort(rng.choice(
                np.arange(1, t), size=n, replace=False)))[0]
                for _ in range(3)]
            out.append((f"{n}_cuts", np.stack(rows)))
        return out

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256),
                                        (512, 256)])
    def test_census_matches_the_dense_mask(self, blocks, causal):
        from lzy_tpu.ops.flash_attention import (
            _HOST_OPS, _KERNEL_OPS, _pair_ranges, block_pair_census,
            segment_slab)

        bq, bkv = blocks
        for name, seg in self._cases():
            b, t = seg.shape
            keep = _dense_keep(t, causal, seg, None)
            tiles = keep.reshape(b, t // bq, bq, t // bkv, bkv)
            needed = tiles.any((2, 4))
            census = block_pair_census(seg, bq, bkv, causal)
            assert census.needed == needed.sum(), name
            assert census.visited == needed.sum(), name

            # the same bounds as a kernel computes them, block by block and
            # from the key side too (the dK/dV kernel's loop), over the
            # lanes the kernels read
            slab = np.asarray(segment_slab(jnp.asarray(seg)))
            start, end = (slab[:, :, lane].astype(np.int64)
                          for lane in (1, 2))
            for q_major, own, other in ((True, bq, bkv), (False, bkv, bq)):
                for row in range(b):
                    for blk in range(t // own):
                        lo_, hi_ = blk * own, (blk + 1) * own - 1
                        docs = (start[row, lo_], end[row, hi_])
                        lo, hi = (int(x) for x in _pair_ranges(
                            lo_, own, other, t // other, docs, causal=causal,
                            q_major=q_major, ops=_HOST_OPS))
                        want_any = needed[row, blk] if q_major \
                            else needed[row, :, blk]
                        others = np.arange(t // other)
                        assert ((others >= lo) & (others < hi)
                                == want_any).all(), (name, q_major, blk)
            # and in the kernels' own arithmetic (traced scalars)
            docs = (start[0, 0], end[0, bq - 1])
            traced = _pair_ranges(jnp.int32(0), bq, bkv, t // bkv,
                                  tuple(jnp.int32(x) for x in docs),
                                  causal=causal, q_major=True,
                                  ops=_KERNEL_OPS)
            host = _pair_ranges(0, bq, bkv, t // bkv, docs, causal=causal,
                                q_major=True, ops=_HOST_OPS)
            assert [int(x) for x in traced] == [int(x) for x in host]

    def test_no_documents_leaves_the_diagonal_alone(self):
        from lzy_tpu.ops.flash_attention import _HOST_OPS, _pair_ranges

        assert _pair_ranges(512, 128, 128, 8, None, causal=True,
                            q_major=True, ops=_HOST_OPS) == (0, 5)
        assert _pair_ranges(512, 128, 128, 8, None, causal=True,
                            q_major=False, ops=_HOST_OPS) == (4, 8)

    def test_no_segments_is_one_document_a_row(self):
        from lzy_tpu.ops.flash_attention import block_pair_census

        # 8 x 8 blocks: the 36 on or under the diagonal
        one = np.zeros((1, 1024), np.int32)
        assert block_pair_census(one, 128, 128, True) == (36, 36)
        assert block_pair_census(one, 128, 128, False) == (64, 64)
