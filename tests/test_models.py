"""Model tests: shapes, sharded end-to-end train steps on the 8-device mesh,
loss decrease — the compute slice of BASELINE configs 2–4 at toy sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lzy_tpu.models import (
    BertConfig,
    LlamaConfig,
    ResNetConfig,
    bert,
    count_params,
    llama,
    resnet,
    unbox,
)
from lzy_tpu.parallel import TrainState, fsdp_mesh, make_train_step, mesh_for


def _train(loss_fn, params, axes, batch, mesh, steps=3, accum_steps=1):
    tx = optax.adam(1e-3)
    step, shard_state, _ = make_train_step(
        loss_fn, tx, mesh=mesh, param_logical_axes=axes,
        batch_logical_axes=("batch",), accum_steps=accum_steps,
    )
    state = shard_state(TrainState.create(params, tx))
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state


class TestLlama:
    def test_forward_shape_and_dtype(self):
        cfg = LlamaConfig.tiny()
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jnp.ones((2, 16), jnp.int32)
        logits = llama.Llama(cfg).apply({"params": params}, tokens)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32  # head always f32

    def test_params_are_annotated(self):
        cfg = LlamaConfig.tiny()
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        assert axes["layer_0"]["attn"]["q_proj"]["kernel"] == (
            "embed", "heads", "head_dim",
        )
        assert axes["embed_tokens"] == ("vocab", "embed")

    def test_fsdp_train_step_loss_decreases(self):
        cfg = LlamaConfig.tiny()
        mesh = fsdp_mesh()
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        batch = {
            "tokens": jax.random.randint(
                jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size
            )
        }
        losses, state = _train(
            llama.make_loss_fn(cfg), params, axes, batch, mesh
        )
        assert losses[-1] < losses[0]
        # fsdp actually shards the embed table over the mesh
        emb = state.params["embed_tokens"]
        assert emb.sharding.spec[1] == "fsdp"

    def test_tp_plus_fsdp_mesh(self):
        cfg = LlamaConfig.tiny()
        mesh = mesh_for(tp=2, fsdp=-1)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        batch = {"tokens": jnp.ones((4, 16), jnp.int32)}
        losses, state = _train(
            llama.make_loss_fn(cfg), params, axes, batch, mesh, steps=2
        )
        gate = state.params["layer_0"]["mlp"]["gate_proj"]["kernel"]
        assert gate.sharding.spec == jax.sharding.PartitionSpec("fsdp", "tp")

    def test_ring_attention_path_matches_dense(self):
        cfg_dense = LlamaConfig.tiny()
        cfg_ring = LlamaConfig.tiny()
        cfg_ring = type(cfg_ring)(**{
            **cfg_ring.__dict__, "use_ring_attention": True,
        })
        mesh = mesh_for(sp=8)
        boxed, _ = llama.init_params(cfg_dense, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0,
                                    cfg_dense.vocab_size)
        dense_logits = llama.Llama(cfg_dense).apply({"params": params}, tokens)
        ring_logits = llama.Llama(cfg_ring).apply(
            {"params": params}, tokens, mesh
        )
        np.testing.assert_allclose(
            np.asarray(dense_logits), np.asarray(ring_logits),
            atol=0.1, rtol=0.05,  # bf16 compute tolerance
        )

    def test_llama3_8b_param_count(self):
        cfg = LlamaConfig.llama3_8b()
        # analytic param count ≈ 8.03B (untied lm_head, like Llama-3)
        d, v, l, ff = cfg.d_model, cfg.vocab_size, cfg.n_layers, cfg.d_ff
        attn = d * d + 2 * d * (cfg.n_kv_heads * cfg.head_dim) + d * d
        mlp = 3 * d * ff
        head = 0 if cfg.tie_embeddings else v * d
        total = v * d + l * (attn + mlp + 2 * d) + d + head
        assert 7.9e9 < total < 8.1e9


class TestBert:
    def test_mlm_train_step(self):
        cfg = BertConfig.tiny()
        mesh = fsdp_mesh()
        boxed, axes = bert.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        rng = jax.random.PRNGKey(3)
        tokens = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
        batch = {
            "tokens": tokens,
            "labels": tokens,
            "mlm_mask": (jax.random.uniform(rng, (8, 32)) < 0.15),
        }
        losses, _ = _train(bert.make_loss_fn(cfg), params, axes, batch, mesh)
        assert losses[-1] < losses[0]

    def test_base_config_param_count(self):
        cfg = BertConfig.base()
        boxed, _ = bert.init_params(cfg, jax.random.PRNGKey(0))
        n = count_params(unbox(boxed))
        assert 105e6 < n < 120e6  # BERT-base ≈ 110M


class TestResNet:
    def test_forward_and_train(self):
        cfg = ResNetConfig.tiny()
        mesh = fsdp_mesh()
        boxed, axes = resnet.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        batch = {
            "images": jax.random.normal(jax.random.PRNGKey(4), (8, 32, 32, 3)),
            "labels": jnp.zeros((8,), jnp.int32),
        }
        losses, _ = _train(resnet.make_loss_fn(cfg), params, axes, batch,
                           mesh, steps=3)
        assert losses[-1] < losses[0]

    def test_resnet50_param_count(self):
        cfg = ResNetConfig.resnet50()
        boxed, _ = resnet.init_params(cfg, jax.random.PRNGKey(0), image_size=64)
        n = count_params(unbox(boxed))
        assert 23e6 < n < 28e6  # ResNet-50 ≈ 25.5M


class TestGeneration:
    def test_decode_matches_full_forward(self):
        """KV-cache decoding must produce the same greedy continuation as
        repeatedly running the full (cacheless) forward."""
        from lzy_tpu.models import generate as generate_fn

        cfg = LlamaConfig.tiny(vocab_size=64)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        prompt = jnp.array([[5, 9, 3]], jnp.int32)

        out = generate_fn(cfg, params, prompt, max_new_tokens=4)
        assert out.shape == (1, 7)

        # reference: greedy with the full forward each step
        model = llama.Llama(cfg)
        seq = prompt
        for _ in range(4):
            logits = model.apply({"params": params}, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    def test_eos_padding(self):
        from lzy_tpu.models import generate as generate_fn

        cfg = LlamaConfig.tiny(vocab_size=16)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(1))
        params = unbox(boxed)
        prompt = jnp.zeros((2, 2), jnp.int32)
        out = generate_fn(cfg, params, prompt, max_new_tokens=3,
                                eos_token=1)
        assert out.shape == (2, 5)

    def test_sampled_generation_shape(self):
        from lzy_tpu.models import generate as generate_fn

        cfg = LlamaConfig.tiny(vocab_size=32)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(2))
        params = unbox(boxed)
        out = generate_fn(
            cfg, params, jnp.ones((2, 2), jnp.int32), max_new_tokens=5,
            temperature=0.8, rng=jax.random.PRNGKey(7),
        )
        assert out.shape == (2, 7)
        assert int(out.max()) < 32

    def test_prompt_overflow_rejected(self):
        from lzy_tpu.models import generate as generate_fn

        cfg = LlamaConfig.tiny()
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="exceeds"):
            generate_fn(cfg, unbox(boxed),
                              jnp.zeros((1, 10), jnp.int32),
                              max_new_tokens=cfg.max_seq_len)


class TestLlamaMoe:
    def test_moe_llama_trains(self):
        cfg = LlamaConfig.tiny()
        cfg = dataclasses.replace(cfg, n_experts=4, moe_top_k=2)
        mesh = fsdp_mesh()
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        assert "moe" in params["layer_0"], "MoE layer missing"
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
        losses, _ = _train(llama.make_loss_fn(cfg), params, axes, batch, mesh)
        assert losses[-1] < losses[0]


class TestSequenceParallelTraining:
    def test_train_step_through_ring_attention(self):
        """Long-context training is first-class: a full sharded TRAIN step
        (fwd + bwd + optimizer) differentiates through the ppermute ring
        over an sp mesh, with the batch's sequence dim sharded."""
        cfg = LlamaConfig.tiny()
        cfg = type(cfg)(**{**cfg.__dict__, "use_ring_attention": True})
        mesh = mesh_for(sp=4, fsdp=2)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tx = optax.adam(1e-3)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(params, tx))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size)}
        losses = []
        for _ in range(4):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        # the batch really trains with its sequence dim on the sp axis
        emb = state.params["embed_tokens"]
        assert "fsdp" in str(emb.sharding.spec)


class TestUlyssesInModel:
    def test_ulysses_path_matches_dense(self):
        cfg_dense = LlamaConfig.tiny()
        cfg_u = type(cfg_dense)(**{
            **cfg_dense.__dict__, "use_ulysses_attention": True,
        })
        mesh = mesh_for(sp=4, fsdp=2)  # tiny() has 4 heads: heads % sp == 0
        boxed, _ = llama.init_params(cfg_dense, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0,
                                    cfg_dense.vocab_size)
        dense_logits = llama.Llama(cfg_dense).apply({"params": params}, tokens)
        u_logits = llama.Llama(cfg_u).apply({"params": params}, tokens, mesh)
        np.testing.assert_allclose(
            np.asarray(dense_logits), np.asarray(u_logits),
            atol=0.1, rtol=0.05,  # bf16 compute tolerance
        )

    def test_train_step_through_ulysses(self):
        cfg = LlamaConfig.tiny()
        cfg = type(cfg)(**{**cfg.__dict__, "use_ulysses_attention": True})
        mesh = mesh_for(sp=4, fsdp=2)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        tx = optax.adam(1e-3)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(unbox(boxed), tx))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size)}
        losses = []
        for _ in range(4):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]


class TestT5:
    def _cfg(self):
        from lzy_tpu.models.t5 import T5Config

        return T5Config.tiny(vocab_size=97)

    def test_loss_and_grads_finite(self):
        import optax

        from lzy_tpu.models import unbox
        from lzy_tpu.models.t5 import init_params, make_loss_fn

        cfg = self._cfg()
        boxed, axes = init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        batch = {
            "enc_tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12),
                                             0, cfg.vocab_size),
            "dec_tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 8),
                                             0, cfg.vocab_size),
            "enc_mask": jnp.ones((2, 12), bool),
        }
        loss, grads = jax.value_and_grad(make_loss_fn(cfg))(params, batch)
        assert jnp.isfinite(loss)
        for leaf in jax.tree_util.tree_leaves(grads):
            assert jnp.all(jnp.isfinite(leaf))

    def test_decoder_is_causal(self):
        """Changing a future decoder token must not change earlier logits."""
        from lzy_tpu.models import unbox
        from lzy_tpu.models.t5 import T5, init_params

        cfg = self._cfg()
        boxed, _ = init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        enc = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, 97)
        dec = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, 97)
        dec2 = dec.at[0, -1].set((dec[0, -1] + 1) % 97)
        model = T5(cfg)
        l1 = model.apply({"params": params}, enc, dec)
        l2 = model.apply({"params": params}, enc, dec2)
        assert jnp.allclose(l1[:, :-1], l2[:, :-1], atol=1e-5)
        # but the encoder DOES influence everything
        enc2 = enc.at[0, 0].set((enc[0, 0] + 1) % 97)
        l3 = model.apply({"params": params}, enc2, dec)
        assert not jnp.allclose(l1, l3, atol=1e-5)

    def test_enc_mask_hides_padding(self):
        from lzy_tpu.models import unbox
        from lzy_tpu.models.t5 import T5, init_params

        cfg = self._cfg()
        boxed, _ = init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        enc = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, 97)
        dec = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 97)
        mask = jnp.array([[True, True, True, True, False, False]])
        model = T5(cfg)
        base = model.apply({"params": params}, enc, dec, mask)
        # mutate only the masked-out positions: logits must be identical
        enc_mut = enc.at[0, 4:].set((enc[0, 4:] + 3) % 97)
        same = model.apply({"params": params}, enc_mut, dec, mask)
        assert jnp.allclose(base, same, atol=1e-6)

    def test_cached_generation_matches_full_forward(self):
        """Greedy decode through the KV cache must reproduce the argmax chain
        of repeated full (non-decode) forwards — the strongest equivalence
        check for the cache."""
        from lzy_tpu.models import unbox
        from lzy_tpu.models.t5 import T5, init_params, t5_generate

        cfg = self._cfg()
        boxed, _ = init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        enc = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 97)

        gen = t5_generate(cfg, params, enc, max_new_tokens=5)

        model = T5(cfg)
        dec = jnp.full((2, 1), cfg.bos_token, jnp.int32)
        ref = []
        for _ in range(5):
            logits = model.apply({"params": params}, enc, dec)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            ref.append(nxt[:, None])
            dec = jnp.concatenate([dec, nxt[:, None]], axis=1)
        assert jnp.array_equal(gen, jnp.concatenate(ref, axis=1))

    def test_shards_on_mesh(self):
        import optax

        from lzy_tpu.models import unbox
        from lzy_tpu.models.t5 import T5Config, init_params, make_loss_fn
        from lzy_tpu.parallel import TrainState, make_train_step, mesh_for

        # every sharded dim must divide the mesh axes (vocab over tp=2 etc.)
        cfg = T5Config.tiny(vocab_size=128)
        boxed, axes = init_params(cfg, jax.random.PRNGKey(0))
        mesh = mesh_for(dp=2, fsdp=2, tp=2)
        step, shard_state, _ = make_train_step(
            make_loss_fn(cfg), optax.adamw(1e-3), mesh=mesh,
            param_logical_axes=axes,
            # a single prefix covers every batch leaf (both are [B, T])
            batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(unbox(boxed), optax.adamw(1e-3)))
        batch = {
            "enc_tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                             0, cfg.vocab_size),
            "dec_tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 16),
                                             0, cfg.vocab_size),
        }
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]


class TestSampling:
    def _logits(self):
        # token 3 dominant, then 1, then 0; tokens 2,4 negligible
        return jnp.array([[1.0, 2.0, -5.0, 4.0, -6.0]])

    def test_top_k_restricts_support(self):
        from lzy_tpu.models.generate import sample_token

        seen = set()
        rng = jax.random.PRNGKey(0)
        for _ in range(40):
            tok, rng = sample_token(self._logits(), 1.0, rng, top_k=2)
            seen.add(int(tok[0]))
        assert seen <= {1, 3}
        assert 3 in seen

    def test_top_p_keeps_nucleus_only(self):
        from lzy_tpu.models.generate import sample_token

        # softmax of [1,2,-5,4,-6] ≈ [.045,.122,.0001,.832,...]: p=.9 keeps
        # {3,1}; p tiny keeps only the argmax
        seen = set()
        rng = jax.random.PRNGKey(1)
        for _ in range(40):
            tok, rng = sample_token(self._logits(), 1.0, rng, top_p=0.9)
            seen.add(int(tok[0]))
        assert seen <= {1, 3}
        tok, _ = sample_token(self._logits(), 1.0, jax.random.PRNGKey(2),
                              top_p=0.01)
        assert int(tok[0]) == 3

    def test_greedy_ignores_filters(self):
        from lzy_tpu.models.generate import sample_token

        tok, _ = sample_token(self._logits(), 0.0, jax.random.PRNGKey(0),
                              top_k=1, top_p=0.1)
        assert int(tok[0]) == 3

    def test_generate_accepts_sampling_filters(self):
        from lzy_tpu.models import generate, llama, unbox

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        out = generate(cfg, params, jnp.array([[3, 5]], jnp.int32),
                       max_new_tokens=3, temperature=0.8, top_k=10,
                       top_p=0.95)
        assert out.shape == (1, 5)

    def test_top_k_zero_is_disabled_not_a_crash(self):
        from lzy_tpu.models.generate import sample_token

        tok, _ = sample_token(self._logits(), 1.0, jax.random.PRNGKey(0),
                              top_k=0)
        assert 0 <= int(tok[0]) < 5


class TestPackedDocuments:
    """Segment-masked attention + per-document positions: a packed row must
    behave exactly like its documents run separately."""

    def test_packed_forward_equals_per_document(self):
        cfg = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=128),
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        model = llama.Llama(cfg)

        rng = np.random.default_rng(0)
        doc_a = rng.integers(0, 128, 24)
        doc_b = rng.integers(0, 128, 40)
        packed = jnp.asarray(np.concatenate([doc_a, doc_b]))[None, :]
        segments = jnp.asarray(
            np.concatenate([np.zeros(24, np.int32), np.ones(40, np.int32)])
        )[None, :]

        packed_logits = model.apply({"params": params}, packed, None,
                                    segments)
        la = model.apply({"params": params}, jnp.asarray(doc_a)[None, :])
        lb = model.apply({"params": params}, jnp.asarray(doc_b)[None, :])
        np.testing.assert_allclose(
            np.asarray(packed_logits[0, :24]), np.asarray(la[0]),
            atol=2e-4, rtol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(packed_logits[0, 24:]), np.asarray(lb[0]),
            atol=2e-4, rtol=2e-4,
        )

    def test_flash_path_matches_fallback_packed(self):
        cfg = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=128),
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, 128, (2, 256))
        )
        segments = jnp.asarray(
            np.repeat(np.arange(4), 64)[None, :].repeat(2, 0)
        )
        base = llama.Llama(cfg).apply({"params": params}, tokens, None,
                                      segments)
        flash_cfg = dataclasses.replace(cfg, use_flash_kernel=True)
        flashed = llama.Llama(flash_cfg).apply({"params": params}, tokens,
                                               None, segments)
        np.testing.assert_allclose(np.asarray(flashed), np.asarray(base),
                                   atol=2e-4, rtol=2e-4)

    def test_loss_masks_document_boundaries(self):
        cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32,
                                  param_dtype=jnp.float32)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        loss_fn = llama.make_loss_fn(cfg)
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, 64, (2, 32))
        )
        segments = jnp.zeros((2, 32), jnp.int32).at[:, 16:].set(1)
        # boundary-masked packed loss == mean of the two per-document losses
        # over the same model (manual check: identical token count per doc)
        packed = float(loss_fn(params, {"tokens": tokens,
                                        "segments": segments}))
        explicit_mask = np.ones((2, 32), bool)
        # shifted mask index 15 = full index 16: target token 16 is the
        # first of document 1, predicted from document 0 — the boundary
        explicit_mask[:, 16] = False
        manual = float(loss_fn(params, {
            "tokens": tokens, "segments": segments,
            "mask": jnp.asarray(explicit_mask),
        }))
        assert abs(packed - manual) < 1e-6

    def test_train_step_with_segments_decreases_loss(self):
        cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=64))
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (8, 64))
        )
        segments = jnp.asarray(
            np.repeat(np.arange(2), 32)[None, :].repeat(8, 0)
        )
        mesh = fsdp_mesh()
        losses, _ = _train(
            llama.make_loss_fn(cfg, mesh), params, axes,
            {"tokens": tokens, "segments": segments}, mesh, steps=4,
        )
        assert losses[-1] < losses[0]


class TestRematPolicy:
    """What a rematerialised layer keeps changes the backward's work, never
    its numbers: the kept values are the ones it would have recomputed."""

    @staticmethod
    def _loss_and_grads(cfg):
        params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
        tokens = jnp.asarray(
            np.random.default_rng(4).integers(0, 128, (2, 128)))
        segments = jnp.asarray(
            np.repeat(np.arange(2), 64)[None, :].repeat(2, 0))
        return jax.jit(jax.value_and_grad(llama.make_loss_fn(cfg)))(
            params, {"tokens": tokens, "segments": segments})

    @pytest.mark.parametrize("flash", [True, False],
                             ids=["flash_kernel", "chunked"])
    @pytest.mark.parametrize("fused_ce", [True, False],
                             ids=["fused_ce", "logits"])
    def test_loss_and_every_gradient_equal_under_each_policy(self, flash,
                                                             fused_ce):
        base = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=128), dtype=jnp.float32,
            param_dtype=jnp.float32, use_flash_kernel=flash,
            fused_ce=fused_ce, remat=True)
        assert base.remat_policy == "dots"     # the default keeps results
        want_loss, want = self._loss_and_grads(base)
        for other in (dataclasses.replace(base, remat_policy="nothing"),
                      dataclasses.replace(base, remat=False)):
            loss, grads = self._loss_and_grads(other)
            assert float(loss) == float(want_loss)
            for (path, a), b in zip(
                    jax.tree_util.tree_leaves_with_path(want),
                    jax.tree_util.tree_leaves(grads)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=jax.tree_util.keystr(path))

    def test_kept_results_survive_the_sharded_attention_wrapper(self):
        # under a mesh the kernel runs inside shard_map: the names must
        # still reach the policy, and the numbers must still be the same
        mesh = fsdp_mesh()
        base = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=128), dtype=jnp.float32,
            param_dtype=jnp.float32, use_flash_kernel=True, fused_ce=True,
            remat=True)
        params = unbox(llama.init_params(base, jax.random.PRNGKey(0))[0])
        tokens = jnp.asarray(
            np.random.default_rng(5).integers(0, 128, (8, 128)))
        batch = {"tokens": tokens}

        def run(cfg):
            return jax.jit(jax.value_and_grad(
                llama.make_loss_fn(cfg, mesh)))(params, batch)

        want_loss, want = run(base)
        loss, grads = run(dataclasses.replace(base, remat_policy="nothing"))
        assert abs(float(loss) - float(want_loss)) < 1e-6
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)

    @pytest.mark.parametrize("axes,batch,per_shard", [
        ({"fsdp": -1}, 8, True), ({"fsdp": -1}, 6, False),
        ({"dp": 2, "fsdp": 2, "tp": 2}, 8, True),
    ], ids=["batch_divides", "batch_does_not", "dp_fsdp_tp"])
    def test_fused_loss_on_a_mesh_equals_the_dense_loss_on_one_device(
            self, axes, batch, per_shard):
        """The fused loss runs per batch shard where the batch divides the
        mesh's batch axes, with the head's gradient summed across the
        devices by one ``psum_scatter`` (and a ``psum`` over a batch axis
        the head is not laid over: dp), and under plain ``jit`` where it
        does not: either way the loss and every gradient are the dense
        loss's."""
        mesh = mesh_for(**axes)
        assert (llama._loss_batch_shard(batch, mesh, None) is not None) \
            == per_shard
        dense = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=128), dtype=jnp.float32,
            param_dtype=jnp.float32)
        params = unbox(llama.init_params(dense, jax.random.PRNGKey(0))[0])
        rng = np.random.default_rng(6)
        data = {"tokens": jnp.asarray(rng.integers(0, 128, (batch, 32))),
                "mask": jnp.asarray(rng.integers(0, 2, (batch, 32)))}
        want_loss, want = jax.jit(jax.value_and_grad(
            llama.make_loss_fn(dense)))(params, data)
        loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
            dataclasses.replace(dense, fused_ce=True), mesh)))(params, data)
        assert abs(float(loss) - float(want_loss)) < 1e-6
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                                jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-6, rtol=1e-5,
                err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("policy,kept", [("dots", True),
                                             ("nothing", False)])
    def test_policy_keeps_matmuls_and_the_kernels_results(self, policy,
                                                          kept, capsys):
        """Read off the residuals a checkpointed function's backward takes
        (``print_saved_residuals`` is the public reader)."""
        from jax.ad_checkpoint import checkpoint_name, print_saved_residuals
        from lzy_tpu.ops.flash_attention import SAVED_NAMES

        def layer(x, w):
            y = x @ w
            o = checkpoint_name(jnp.sin(y), SAVED_NAMES[0])
            return jnp.sum(jnp.cos(o))

        f = jax.checkpoint(layer, policy=llama._remat_policy(policy))
        print_saved_residuals(f, jnp.ones((4, 8)), jnp.ones((8, 8)))
        # beyond the arguments: the matmul's result and the named value
        extra = [line for line in capsys.readouterr().out.splitlines()
                 if line.strip() and "argument" not in line]
        assert len(extra) == (2 if kept else 0), extra

    def test_unknown_policy_is_refused(self):
        with pytest.raises(ValueError, match="unknown remat_policy 'all'"):
            llama._remat_policy("all")
        cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=128),
                                  remat=True, remat_policy="all")
        with pytest.raises(ValueError, match="known: .*dots.*nothing"):
            llama.init_params(cfg, jax.random.PRNGKey(0))
