"""Parallel-layer tests on the 8-device virtual CPU mesh (conftest forces
``xla_force_host_platform_device_count=8``) — same XLA partitioner and
collectives as TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lzy_tpu.parallel import (
    MeshSpec,
    TrainState,
    fsdp_mesh,
    make_train_step,
    mesh_for,
    mfu,
    named_sharding,
    ring_attention,
    shard_tree,
    infer_param_logical_axes,
)


def test_eight_devices_available():
    assert jax.device_count() == 8


class TestMesh:
    def test_fsdp_mesh_shape(self):
        mesh = fsdp_mesh()
        assert mesh.shape == {"pp": 1, "dp": 1, "fsdp": 8, "ep": 1,
                              "tp": 1, "sp": 1}

    def test_mixed_mesh(self):
        mesh = mesh_for(tp=2, fsdp=-1)
        assert mesh.shape["tp"] == 2
        assert mesh.shape["fsdp"] == 4

    def test_bad_mesh_rejected(self):
        with pytest.raises(ValueError, match="needs 6 devices"):
            MeshSpec(dp=2, tp=3).build()
        with pytest.raises(ValueError, match="not divisible"):
            MeshSpec(dp=3, fsdp=-1).build()
        with pytest.raises(ValueError, match="one mesh axis"):
            MeshSpec(dp=-1, fsdp=-1).build()


class TestSharding:
    def test_named_sharding_spec(self):
        mesh = fsdp_mesh()
        # activations: batch over (dp, fsdp); params: embed over fsdp, mlp over tp
        assert named_sharding(mesh, "batch", None).spec == P(("dp", "fsdp"), None)
        assert named_sharding(mesh, "embed", "mlp").spec == P("fsdp", "tp")

    def test_shard_tree_places_on_devices(self):
        mesh = fsdp_mesh()
        params = {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))}
        sharded = shard_tree(
            params, mesh, {"w": ("embed", None), "b": (None,)}
        )
        # w's first dim (16) split over 8 fsdp devices → shard shape (2, 8)
        shard_shapes = {s.data.shape for s in sharded["w"].addressable_shards}
        assert shard_shapes == {(2, 8)}
        assert len(sharded["b"].addressable_shards) == 8  # replicated

    def test_infer_logical_axes_picks_largest_dim(self):
        params = {"k": jnp.ones((4, 100)), "v": jnp.ones((3,))}
        axes = infer_param_logical_axes(params)
        assert axes["k"] == (None, "embed")
        assert axes["v"] == (None,)


class TestTrainStep:
    def _setup(self, accum_steps=1):
        mesh = fsdp_mesh()
        params = {
            "w1": jnp.ones((16, 32), jnp.float32) * 0.01,
            "w2": jnp.ones((32, 4), jnp.float32) * 0.01,
        }

        def loss_fn(p, batch):
            x, y = batch["x"], batch["y"]
            h = jnp.tanh(x @ p["w1"])
            logits = h @ p["w2"]
            return jnp.mean((logits - y) ** 2)

        tx = optax.adam(1e-2)
        step, shard_state, batch_sh = make_train_step(
            loss_fn, tx, mesh=mesh,
            param_logical_axes={"w1": (None, "embed"), "w2": ("embed", None)},
            batch_logical_axes=("batch", None),
            accum_steps=accum_steps,
        )
        state = shard_state(TrainState.create(params, tx))
        batch = {
            "x": jnp.ones((16, 16)),
            "y": jnp.zeros((16, 4)),
        }
        return step, state, batch, batch_sh

    def test_loss_decreases(self):
        step, state, batch, _ = self._setup()
        losses = []
        for _ in range(5):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 5

    def test_params_stay_sharded(self):
        step, state, batch, _ = self._setup()
        state, _ = step(state, batch)
        sh = state.params["w1"].sharding
        assert isinstance(sh, NamedSharding)
        assert sh.spec == P(None, "fsdp")

    def test_grad_accumulation_matches_full_batch(self):
        step1, state1, batch, _ = self._setup(accum_steps=1)
        step4, state4, _, _ = self._setup(accum_steps=4)
        s1, m1 = step1(state1, batch)
        s4, m4 = step4(state4, batch)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m4["loss"]), rtol=1e-5
        )
        w1_a = np.asarray(jax.device_get(s1.params["w1"]))
        w1_b = np.asarray(jax.device_get(s4.params["w1"]))
        # adam drives weights through ~0 after one step; relative tolerance is
        # meaningless there, compare absolutely at float32 resolution
        np.testing.assert_allclose(w1_a, w1_b, atol=1e-8)


class TestStepCompilerOptions:
    """The sharded step's compiler options follow the platform and the mesh
    it is handed (``parallel.train.step_compiler_options``)."""

    @staticmethod
    def _mesh(platform, **axes):
        import types

        n = int(np.prod(list(axes.values())))
        devices = np.array(
            [types.SimpleNamespace(platform=platform) for _ in range(n)],
            dtype=object).reshape(tuple(axes.values()))
        return types.SimpleNamespace(devices=devices, shape=dict(axes))

    @pytest.mark.parametrize("platform,axes,spec,expected", [
        ("tpu", {"dp": 1, "fsdp": 4}, P(None, "fsdp"), True),
        # an axis of a tuple entry shards the parameter too
        ("tpu", {"dp": 1, "fsdp": 4}, P(("dp", "fsdp"), None), True),
        ("tpu", {"dp": 1, "fsdp": 1}, P(None, "fsdp"), False),
        # four chips that only split the batch: no gradient reduce-scatter
        ("tpu", {"dp": 4, "fsdp": 1}, P(None, "fsdp"), False),
        ("tpu", {"dp": 1, "fsdp": 4}, P(), False),
        ("cpu", {"dp": 1, "fsdp": 4}, P(None, "fsdp"), False),
    ], ids=["tpu_fsdp4", "tpu_fsdp4_tuple", "tpu_one_chip", "tpu_dp4",
            "tpu_replicated", "cpu_fsdp4"])
    def test_options_follow_platform_and_sharded_axes(
            self, platform, axes, spec, expected):
        from lzy_tpu.parallel import train

        layout = {"w": NamedSharding(mesh_for(8, dp=2, fsdp=4), spec),
                  "b": NamedSharding(mesh_for(8, dp=2, fsdp=4), P())}
        got = train.step_compiler_options(self._mesh(platform, **axes),
                                          layout)
        if expected:
            assert got == train.TPU_SHARDED_STEP_OPTIONS
            assert got is not train.TPU_SHARDED_STEP_OPTIONS   # a copy
        else:
            assert got is None

    def test_cpu_step_is_jitted_with_no_option(self, monkeypatch):
        """On the CPU an option of the TPU compiler is a compile error: the
        step is compiled as it always was, and its first loss is the one the
        arithmetic gives."""
        seen = []
        real_jit = jax.jit

        def spy(fun, **kwargs):
            seen.append(kwargs.get("compiler_options", "absent"))
            return real_jit(fun, **kwargs)

        monkeypatch.setattr(jax, "jit", spy)
        step, state, batch, _ = TestTrainStep()._setup()
        _, metrics = step(state, batch)
        assert seen == [None]
        logit = 32 * 0.01 * np.tanh(16 * 0.01)
        np.testing.assert_allclose(float(metrics["loss"]), logit ** 2,
                                   rtol=1e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_attention(self, causal):
        mesh = mesh_for(sp=8)
        b, h, s, d = 2, 4, 64, 16
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
        k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
        v = jax.random.normal(kv, (b, h, s, d), jnp.float32)

        out = ring_attention(q, k, v, mesh=mesh, causal=causal)

        # dense reference
        scale = d ** -0.5
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            mask = np.tril(np.ones((s, s), bool))
            logits = jnp.where(mask, logits, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_jittable_and_sharded(self):
        mesh = mesh_for(sp=8)
        b, h, s, d = 1, 2, 32, 8
        q = jnp.ones((b, h, s, d))

        @jax.jit
        def run(q):
            return ring_attention(q, q, q, mesh=mesh, causal=True)

        out = run(q)
        assert out.shape == q.shape


def test_mfu_math():
    # 1000 tok/s on a 1B model over 16 v5e chips
    val = mfu(1000.0, 1_000_000_000, 16, peak_tflops=197.0)
    assert 0 < val < 1
    np.testing.assert_allclose(val, 6e12 / (197e12 * 16), rtol=1e-6)


def test_peak_is_looked_up_by_device_kind_and_unknown_kinds_fail():
    from lzy_tpu.parallel.train import chip_peak_tflops

    assert chip_peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="no peak"):
        chip_peak_tflops("cpu")


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        from lzy_tpu.parallel import ulysses_attention

        mesh = mesh_for(sp=8)
        b, h, s, d = 2, 8, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q, k, v = (jax.random.normal(x, (b, h, s, d), jnp.float32) for x in ks)

        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)

        scale = d ** -0.5
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            mask = np.tril(np.ones((s, s), bool))
            logits = jnp.where(mask, logits, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_ring(self):
        from lzy_tpu.parallel import ulysses_attention

        mesh = mesh_for(sp=8)
        b, h, s, d = 1, 8, 128, 8
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(x, (b, h, s, d), jnp.float32) for x in ks)
        a = ulysses_attention(q, k, v, mesh=mesh, causal=True)
        b_out = ring_attention(q, k, v, mesh=mesh, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_out),
                                   atol=3e-5, rtol=3e-5)

    def test_head_divisibility_enforced(self):
        from lzy_tpu.parallel import ulysses_attention

        mesh = mesh_for(sp=8)
        q = jnp.ones((1, 6, 64, 8))  # 6 heads not divisible by sp=8
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, q, q, mesh=mesh)


class TestHybridMesh:
    """Multi-slice ICI x DCN meshes (virtual slices on CPU devices)."""

    def test_dcn_dp_layout_keeps_slices_contiguous(self):
        from lzy_tpu.parallel import hybrid_mesh

        mesh = hybrid_mesh(dcn_dp=2, fsdp=-1)
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "pp": 1, "dp": 2, "fsdp": 4, "ep": 1, "tp": 1, "sp": 1}
        devs = jax.devices()
        # dp index 0 must hold exactly slice 0 (first half of the devices):
        # fsdp collectives then never cross the DCN boundary
        dp0 = set(mesh.devices[0, 0, :, 0, 0, 0].ravel().tolist())
        assert dp0 == set(devs[:4])
        dp1 = set(mesh.devices[0, 1, :, 0, 0, 0].ravel().tolist())
        assert dp1 == set(devs[4:])

    def test_dcn_pp_with_inner_axes(self):
        from lzy_tpu.parallel import hybrid_mesh

        mesh = hybrid_mesh(dcn_pp=2, tp=2, fsdp=2)
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "pp": 2, "dp": 1, "fsdp": 2, "ep": 1, "tp": 2, "sp": 1}
        devs = jax.devices()
        assert set(mesh.devices[0].ravel().tolist()) == set(devs[:4])

    def test_single_slice_falls_back(self):
        from lzy_tpu.parallel import hybrid_mesh, mesh_for

        mesh = hybrid_mesh(fsdp=-1)
        assert mesh.devices.shape == mesh_for(fsdp=-1).devices.shape

    def test_trains_on_hybrid_mesh(self):
        """A sharded train step over a dcn_dp x fsdp hybrid mesh runs and
        learns — the full multi-slice code path minus the physical DCN."""
        import optax

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.parallel import TrainState, hybrid_mesh, make_train_step

        cfg = llama.LlamaConfig.tiny(vocab_size=128)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        mesh = hybrid_mesh(dcn_dp=2, fsdp=2, tp=2)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg), optax.adamw(1e-2), mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"))
        state = shard_state(TrainState.create(unbox(boxed), optax.adamw(1e-2)))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 128)
        losses = []
        for _ in range(4):
            state, m = step(state, {"tokens": tokens})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_errors(self):
        from lzy_tpu.parallel import hybrid_mesh

        with pytest.raises(ValueError, match="not divisible"):
            hybrid_mesh(dcn_dp=3, fsdp=-1)
        with pytest.raises(ValueError, match="may not be -1"):
            hybrid_mesh(dcn_dp=2, dp=-1)
        with pytest.raises(ValueError, match="dcn axes must be >= 1"):
            hybrid_mesh(dcn_dp=-1, fsdp=-1)


class TestSegmentedSequenceParallel:
    """Packed documents under sequence parallelism: ids ride the ring with
    K/V (or all-gather under Ulysses), so documents may straddle shards."""

    @staticmethod
    def _inputs(b=2, h=4, s=64, d=16, seed=0):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
        k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
        v = jax.random.normal(kv, (b, h, s, d), jnp.float32)
        # uneven documents, deliberately NOT aligned to the 8-way shards
        cuts = np.array([13, 30, 47])
        seg = jnp.asarray(
            np.searchsorted(cuts, np.arange(s), side="right")[None, :]
            .repeat(b, 0)
        )
        return q, k, v, seg

    @staticmethod
    def _dense(q, k, v, seg, causal):
        s = q.shape[2]
        scale = q.shape[-1] ** -0.5
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        keep = seg[:, None, :, None] == seg[:, None, None, :]
        if causal:
            keep = keep & np.tril(np.ones((s, s), bool))[None, None]
        logits = jnp.where(keep, logits, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(logits, axis=-1), v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_matches_dense(self, causal):
        mesh = mesh_for(sp=8)
        q, k, v, seg = self._inputs()
        out = ring_attention(q, k, v, mesh=mesh, causal=causal,
                             segment_ids=seg)
        ref = self._dense(q, k, v, seg, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ulysses_matches_dense(self):
        from lzy_tpu.parallel.ulysses import ulysses_attention

        mesh = mesh_for(sp=8)
        q, k, v, seg = self._inputs(h=8)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=True,
                                segment_ids=seg)
        ref = self._dense(q, k, v, seg, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_packed_train_step_on_sp_mesh(self):
        """Differentiate a packed llama train step through ring attention."""
        import dataclasses

        import optax

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.parallel import TrainState, make_train_step

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  use_ring_attention=True)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        mesh = mesh_for(dp=2, sp=4)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg, mesh), optax.adam(1e-3), mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(unbox(boxed),
                                              optax.adam(1e-3)))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, 64, (2, 64))),
            "segments": jnp.asarray(
                np.searchsorted([21, 40], np.arange(64), side="right")
                [None, :].repeat(2, 0)
            ),
        }
        losses = []
        for _ in range(4):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]


class TestEvalStep:
    def test_eval_matches_loss_and_never_mutates_params(self):
        import dataclasses

        import optax

        from lzy_tpu.models import llama
        from lzy_tpu.models.llama import LlamaConfig
        from lzy_tpu.parallel import (
            TrainState, make_eval_step, make_train_step, mesh_for)

        cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=128),
                                  dtype=jnp.float32)
        mesh = mesh_for(8, fsdp=4, tp=2)
        params, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        loss_fn = llama.make_loss_fn(cfg, mesh)
        tx = optax.adamw(1e-2)
        step, shard_state, _ = make_train_step(
            loss_fn, tx, mesh=mesh, param_logical_axes=axes,
            batch_logical_axes=("batch", "seq"), donate=False)
        state = shard_state(TrainState.create(params, tx))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}

        eval_step = make_eval_step(loss_fn, mesh=mesh)
        before = float(eval_step(state.params, batch)["loss"])
        # eval over the SHARDED params equals the direct loss
        direct = float(loss_fn(jax.device_get(state.params), batch))
        np.testing.assert_allclose(before, direct, rtol=1e-5)

        # interleave: train one step, eval again — params still usable
        # (no donation) and the eval loss tracks training
        state, _ = step(state, batch)
        after = float(eval_step(state.params, batch)["loss"])
        assert after < before

    def test_eval_step_dict_metrics(self):
        from lzy_tpu.parallel import make_eval_step, mesh_for

        mesh = mesh_for(8, fsdp=-1)

        def metrics(params, batch):
            x = batch["x"]
            return {"mean": (x * params["w"]).mean(),
                    "max": (x * params["w"]).max()}

        eval_step = make_eval_step(metrics, mesh=mesh,
                                   batch_logical_axes=("batch",))
        out = eval_step({"w": jnp.float32(2.0)},
                        {"x": jnp.arange(8.0)})
        np.testing.assert_allclose(float(out["mean"]), 7.0)
        np.testing.assert_allclose(float(out["max"]), 14.0)


class TestCustomRuleThreading:
    """ADVICE r5: activation anchors and the batch-sharded attention
    wrapper must follow the ACTIVE rule table, not assume DEFAULT_RULES
    and dp/fsdp/tp axis names — a remapped deployment (here: one custom
    'data' axis) used to crash on the missing mesh axes."""

    def _rules(self):
        return {"batch": "data", "embed": "data", "vocab": None,
                "mlp": None, "heads": None, "heads_merged": None,
                "seq": None, "act_embed": None, "act_vocab": None,
                "act_mlp": None, "act_heads": None, "channels_out": None}

    def test_llama_trains_on_remapped_mesh(self):
        from jax.sharding import Mesh

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny(vocab_size=128)
        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        rules = self._rules()
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        tx = optax.adamw(1e-3)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg, mesh, rules=rules), tx, mesh=mesh,
            param_logical_axes=axes, rules=rules,
            batch_logical_axes=("batch", "seq"))
        state = shard_state(TrainState.create(unbox(boxed), tx))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (16, 32), 0, cfg.vocab_size)}
        state, metrics = step(state, batch)
        assert 0.0 < float(metrics["loss"]) < 20.0
        emb = state.params["embed_tokens"]
        assert "data" in str(emb.sharding.spec), emb.sharding.spec

    def test_remapped_matches_default_rules_numerics(self):
        """Sharding rules relocate data; they must not change the loss."""
        from lzy_tpu.models import llama, unbox
        from lzy_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny(vocab_size=128)
        boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}

        from jax.sharding import Mesh

        default_mesh = mesh_for(8, fsdp=-1)
        ref = float(jax.jit(llama.make_loss_fn(cfg, default_mesh))(
            params, batch))
        custom = Mesh(np.array(jax.devices()[:8]), ("data",))
        got = float(jax.jit(llama.make_loss_fn(
            cfg, custom, rules=self._rules()))(params, batch))
        np.testing.assert_allclose(got, ref, rtol=1e-5)

    def test_freeze_rules_roundtrip(self):
        from lzy_tpu.parallel.sharding import freeze_rules

        rules = {"batch": ("dp", "fsdp"), "embed": "fsdp", "seq": None}
        frozen = freeze_rules(rules)
        assert hash(frozen) is not None
        assert dict(frozen) == rules
        assert freeze_rules(None) is None and freeze_rules({}) is None
