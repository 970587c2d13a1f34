"""Deviceless TPU compiles: what the chip's compiler says, without the chip.

The CPU dryrun proves the sharded step executes; these tests prove the
*TPU* compiler (same libtpu the chip uses, via
``jax.experimental.topologies``) takes the programs of the main path:

- the sharded train step schedules without collective pathologies: a
  single-chip module contains no collectives at all, and an fsdp module's
  all-gather traffic stays within the parameter-gathering budget (an
  activation resharding cliff blows straight through that bound);
- the Pallas kernels compile at Llama-3-8B widths with ``interpret=False``:
  flash attention forward and backward up to the longest length its wrapper
  accepts, the paged-attention read ``"auto"`` resolves to (the Pallas decode
  and chunk kernels over float pools, lax over int8), and one paged decode
  step and one prefill step of a two-layer model at full width.

Interpret mode cannot see a block shape the TPU lowering refuses or a kernel
that runs out of VMEM; these compiles can, at about two seconds each and no
chip time. All of them live in this one file, and the topology is described
inside a fixture: only one process may load libtpu, so nothing here touches it
while a module is imported (``/opt/skills/guides/on-chip-measurement``).
"""

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from lzy_tpu.models import count_params, llama, unbox
from lzy_tpu.models.common import param_logical_axes


@pytest.fixture(scope="module")
def topo():
    """A described, unattached v5e 2x2. The persistent compilation cache is
    off while this module runs: such a compile is written to it but cannot
    be read back without a chip, and the next run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or another holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _small_cfg():
    # small-but-not-tiny: at toy sizes the partitioner makes degenerate
    # choices that would make the traffic bound meaningless
    return llama.LlamaConfig(
        vocab_size=4096, d_model=256, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=512, max_seq_len=256, remat=False, tie_embeddings=True,
    )


def _compile(cfg, devices, mesh_axes, batch_shape, packed=False):
    import optax

    from lzy_tpu.parallel import MeshSpec, TrainState, make_train_step

    mesh = MeshSpec(**mesh_axes).build(devices)
    boxed = jax.eval_shape(
        lambda k: llama.init_params(cfg, k)[0], jax.random.PRNGKey(0))
    params = unbox(boxed)
    tx = optax.adamw(3e-4)
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    step, _, batch_sharding = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=param_logical_axes(boxed),
        batch_logical_axes=("batch", "seq"))
    row = jax.ShapeDtypeStruct(batch_shape, jnp.int32,
                               sharding=batch_sharding)
    batch = {"tokens": row, "segments": row} if packed else {"tokens": row}

    from tools.aot_analysis import StderrCapture, collective_census

    with StderrCapture() as scan:
        compiled = step.lower(state, batch).compile()
    return compiled, collective_census(compiled.as_text()), scan.text()


def test_single_chip_module_has_no_collectives(topo):
    cfg = _small_cfg()
    compiled, census, stderr = _compile(
        cfg, list(topo.devices)[:1], {"fsdp": -1}, (4, 256))
    assert census == {}, f"single-chip module emits collectives: {census}"
    assert "Involuntary full rematerialization" not in stderr
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    assert ca.get("flops", 0) > 0


def test_fsdp_module_collectives_are_the_expected_ones(topo):
    cfg = _small_cfg()
    compiled, census, stderr = _compile(
        cfg, list(topo.devices), {"fsdp": -1}, (8, 256))
    assert "Involuntary full rematerialization" not in stderr

    # fsdp's legal collective set: param all-gathers (fwd + bwd), grad
    # reduction (all-reduce or reduce-scatter), scalar metric reductions.
    # An all-to-all means the partitioner invented a resharding nobody
    # asked for.
    assert "all-to-all" not in census, census
    assert "all-gather" in census, "fsdp must gather params"
    assert ("all-reduce" in census) or ("reduce-scatter" in census), (
        "fsdp must reduce grads")

    # traffic budget: fsdp gathers each param in bf16 for fwd, bwd, and a
    # few extra uses (the tied embedding feeds embed + head + both
    # backwards) — a handful of full-tree equivalents. Before the
    # activation anchors (models/llama.py _anchor) the partitioner
    # batch-all-gathered [B,T,V] masks instead: 1459 MB here, 164x the
    # tree — this bound pins that class of regression with huge margin.
    boxed = jax.eval_shape(
        lambda k: llama.init_params(cfg, k)[0], jax.random.PRNGKey(0))
    param_bytes = count_params(unbox(boxed)) * 4  # f32 master params
    ag_bytes = census["all-gather"]["bytes"]
    assert ag_bytes <= 6 * param_bytes, (
        f"all-gather traffic {ag_bytes/1e6:.1f} MB exceeds 6x param bytes "
        f"{6*param_bytes/1e6:.1f} MB — unexpected gathers beyond fsdp's "
        f"param fwd+bwd budget")


# -- what a rematerialised layer's backward runs again -------------------------

@pytest.mark.parametrize("scope,kind", [
    ("transpose(jvp(Llama))/jvp(Llama)/checkpoint/rematted_computation/"
     "layer_0/mlp/gate_proj/dot_general", "dot_general"),
    ("transpose(jvp(Llama))/jvp(Llama)/checkpoint/rematted_computation/"
     "layer_3/attn/shard_map/pallas_call", "pallas_call"),
    ("transpose(jvp(Llama))/jvp(Llama)/checkpoint/rematted_computation/"
     "layer_3/mlp_norm/rsqrt", "other"),
    # the backward's own matmul, and the forward's: not run a second time
    ("transpose(jvp(Llama))/jvp(Llama)/checkpoint/layer_0/mlp/gate_proj/"
     "dot_general", None),
    ("jvp(Llama)/layer_0/attn/shard_map/pallas_call", None),
], ids=["matmul", "kernel", "other", "backward_own", "forward"])
def test_recompute_census_reads_the_scope_from_op_name(scope, kind):
    from tools.aot_analysis import recompute_census

    hlo = (f'  %fusion.1 = bf16[2,4096]{{1,0}} fusion(%p), kind=kOutput, '
           f'metadata={{op_name="jit(step)/{scope}" stack_frame_id=3}}\n'
           f'  %add.2 = f32[] add(%a, %b)\n')
    want = {"dot_general": 0, "pallas_call": 0, "other": 0}
    if kind:
        want[kind] = 1
    assert recompute_census(hlo) == want


#: tokens a block of the fused loss in ``remat_steps``: four blocks a chip
_LOSS_ROWS = 1024


@pytest.fixture(scope="module")
def remat_steps(topo):
    """The training cell's kind of step (flash kernels, fused cross-entropy,
    packed rows, fsdp=4) at two layers and small widths, compiled once under
    each spelling of ``remat_policy``: ``(recompute census, temp bytes,
    partitioner's log, scheduled collectives, compiled text)`` by policy.
    Since PR 50 these compiles carry
    ``parallel.train.TPU_SHARDED_STEP_OPTIONS``: the mesh's devices are TPUs
    and fsdp=4 shards the parameters. Since PR 54 the loss is one loop over
    blocks of tokens: at these widths its rule would take a chip's 4,096
    tokens in one block and leave no loop to read, so the budget of a
    block's logits is set here to what makes four."""
    from lzy_tpu.ops import chunked_ce, interpret
    from tools.aot_analysis import collectives_scheduled, recompute_census

    base = llama.LlamaConfig(
        vocab_size=4096, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=1024, max_seq_len=2048, use_flash_kernel=True, fused_ce=True)
    assert base.remat and base.remat_policy == "dots"   # the defaults
    out = {}
    # the kernels are compiled, not interpreted, as on the chip
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(interpret, "_process_wide", False)
        patch.setattr(chunked_ce, "_LOGITS_BYTES",
                      _LOSS_ROWS * base.vocab_size * 4)
        for policy in ("dots", "nothing"):
            cfg = dataclasses.replace(base, remat_policy=policy)
            compiled, _, stderr = _compile(
                cfg, list(topo.devices), {"fsdp": 4}, (8, 2048), packed=True)
            hlo = compiled.as_text()
            assert "tpu_custom_call" in hlo
            out[policy] = (recompute_census(hlo),
                           compiled.memory_analysis().temp_size_in_bytes,
                           stderr, collectives_scheduled(hlo), hlo)
    return out


def test_default_policy_recomputes_no_matmul_and_no_kernel(remat_steps):
    census = remat_steps["dots"][0]
    assert census["dot_general"] == 0, census
    assert census["pallas_call"] == 0, census
    # norms, RoPE, silu(gate) * up are still run again: reads of what is kept
    assert census["other"] > 0, census


def test_nothing_policy_recomputes_both_in_less_memory(remat_steps):
    census, temp = remat_steps["nothing"][:2]
    assert census["dot_general"] > 0, census
    assert census["pallas_call"] > 0, census
    assert temp < remat_steps["dots"][1]


@pytest.mark.parametrize("policy", ["dots", "nothing"])
def test_remat_steps_partition_without_a_resharding_cliff(policy,
                                                          remat_steps):
    assert "Involuntary full rematerialization" not in remat_steps[policy][2]


# -- the sharded step's compiler options, and what they schedule --------------

_START = ('  %async-collective-start.3 = (bf16[256,8]{1,0}, bf16[1024,8]{1,0}) '
          'fusion(%p.1), kind=kCustom, calls=%fused_computation.7\n')
_CANNED = (
    'HloModule jit_step, is_scheduled=true\n\n'
    '%fused_computation.7 (param_0.1: bf16[256,8]) -> (bf16[256,8], '
    'bf16[1024,8]) {\n'
    '  %param_0.1 = bf16[256,8]{1,0} parameter(0)\n'
    '  %all-gather.5 = bf16[1024,8]{1,0} all-gather(%param_0.1), '
    'channel_id=71, replica_groups=[1,4]<=[4], dimensions={0}\n'
    '  ROOT %custom-call.1 = (bf16[256,8]{1,0}, bf16[1024,8]{1,0}) '
    'custom-call(%all-gather.5), custom_call_target="AsyncCollectiveStart"\n'
    '}\n\n'
    '%async_collective_fusion.9 (param_0.2: bf16[256,8]) -> bf16[1024,8] {\n'
    '  %param_0.2 = bf16[256,8]{1,0} parameter(0)\n'
    '  ROOT %all-gather.6 = bf16[1024,8]{1,0} all-gather(%param_0.2), '
    'channel_id=71, replica_groups=[1,4]<=[4], dimensions={0}\n'
    '}\n\n'
    '%all-reduce-scatter.2 (input.2: bf16[1024,8]) -> bf16[256,8] {\n'
    '  %input.2 = bf16[1024,8]{1,0} parameter(0)\n'
    '  %all-reduce.4 = bf16[1024,8]{1,0} all-reduce(%input.2), '
    'channel_id=80, replica_groups={{0,1,2,3}}, to_apply=%add.1\n'
    '  ROOT %dynamic-slice.1 = bf16[256,8]{1,0} dynamic-slice(%all-reduce.4, '
    '%c.0, %c.1), dynamic_slice_sizes={256,8}\n'
    '}\n\n'
    'ENTRY %main.1_spmd (p.1: bf16[256,8]) -> bf16[256,8] {\n'
    '  %p.1 = bf16[256,8]{1,0} parameter(0)\n'
    '{start}'
    '  %fusion.9 = bf16[1024,8]{1,0} fusion(%p.1), kind=kOutput, '
    'calls=%async_collective_fusion.9\n'
    '  %async-collective-done.3 = bf16[1024,8]{1,0} fusion(%p.1), '
    'kind=kCustom, calls=%fused_computation.7\n'
    '  %all-reduce.9 = f32[]{:T(128)} all-reduce(%s.1), channel_id=90, '
    'replica_groups={{0,1,2,3}}, to_apply=%add.1\n'
    '  ROOT %fusion.2 = bf16[256,8]{1,0} fusion(%fusion.9), kind=kCustom, '
    'calls=%all-reduce-scatter.2\n'
    '}\n')


def test_collectives_scheduled_reads_start_pairs_and_fused_reduce_scatters():
    from tools.aot_analysis import collectives_scheduled

    # the gather's channel is in its start, its done and the compute fusion
    # that carries its steps: one asynchronous all-gather
    assert collectives_scheduled(_CANNED.replace("{start}", _START)) == {
        "all-gather": {"async": 1, "sync": 0},
        "all-reduce": {"async": 0, "sync": 1},
        "reduce-scatter": {"async": 0, "sync": 1}}
    # without a start the same channel is a synchronous one
    assert collectives_scheduled(_CANNED.replace("{start}", ""))[
        "all-gather"] == {"async": 0, "sync": 1}


def test_collective_census_counts_a_channel_once():
    from tools.aot_analysis import collective_census

    census = collective_census(_CANNED.replace("{start}", _START))
    # channel 71 is written in two fusion bodies
    assert census["all-gather"] == {"count": 1, "bytes": 1024 * 8 * 2}
    assert census["all-reduce"]["count"] == 2          # channels 80 and 90


def test_tpu_mesh_gets_the_options_and_one_chip_none(topo):
    from lzy_tpu.parallel import MeshSpec, train
    from lzy_tpu.parallel.sharding import tree_shardings

    def options(devices):
        mesh = MeshSpec(fsdp=-1).build(devices)
        layout = tree_shardings(mesh, {"w": ("embed", None)}, None)
        return train.step_compiler_options(mesh, layout)

    assert options(list(topo.devices)) == train.TPU_SHARDED_STEP_OPTIONS
    assert options(list(topo.devices)[:1]) is None


def test_the_options_reach_the_compile(topo, monkeypatch):
    """An option the compiler does not know fails the compile of a sharded
    step on a TPU mesh, and not the one-chip step, which gets none."""
    from lzy_tpu.parallel import train

    monkeypatch.setattr(train, "TPU_SHARDED_STEP_OPTIONS",
                        {"xla_no_such_option_of_any_compiler": True})
    with pytest.raises(Exception, match="No such compile option"):
        _compile(_small_cfg(), list(topo.devices), {"fsdp": -1}, (8, 256))
    _compile(_small_cfg(), list(topo.devices)[:1], {"fsdp": -1}, (4, 256))


@pytest.mark.parametrize("policy", ["dots", "nothing"])
def test_sharded_step_schedules_its_gathers_asynchronously(policy,
                                                           remat_steps):
    """The parameter all-gathers ride beside the matmuls; the gradients'
    reduce-scatters are fusions on the core's own timeline, as the options
    leave them (PERF.md section 6, PR 50: the compiler's asynchronous form
    cost the matmuls that carry it what it hid)."""
    scheduled = remat_steps[policy][3]
    gathers = scheduled["all-gather"]
    assert gathers["async"] > 4 * gathers["sync"], scheduled
    assert scheduled["reduce-scatter"]["async"] == 0, scheduled
    assert "all-to-all" not in scheduled


# -- the fused loss: one local loop, three products, one reduce-scatter -------

_CANNED_LOOPS = (
    'HloModule jit_step, is_scheduled=true\n\n'
    '%fused_computation.1 (p.0: bf16[8,4]) -> f32[8,8] {\n'
    '  %p.0 = bf16[8,4]{1,0} parameter(0)\n'
    '  ROOT %convolution.1 = f32[8,8]{1,0} convolution(%p.0, %p.0), '
    'dim_labels=bf_oi->bf\n'
    '}\n\n'
    '%body.1 (t.0: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {\n'
    '  %t.0 = (s32[], bf16[8,4]{1,0}) parameter(0)\n'
    '  %fusion.1 = f32[8,8]{1,0} fusion(%x.1), kind=kOutput, '
    'calls=%fused_computation.1\n'
    '{inside}'
    '  ROOT %tuple.1 = (s32[], bf16[8,4]{1,0}) tuple(%i.1, %x.1)\n'
    '}\n\n'
    'ENTRY %main.1_spmd (p.1: bf16[8,4]) -> bf16[8,4] {\n'
    '  %p.1 = bf16[8,4]{1,0} parameter(0)\n'
    '  %while.1 = (s32[], bf16[8,4]{1,0}) while(%tuple.9), '
    'condition=%cond.1, body=%body.1\n'
    '  ROOT %all-reduce.9 = f32[]{:T(128)} all-reduce(%s.1), channel_id=1, '
    'replica_groups={{0,1,2,3}}, to_apply=%add.1\n'
    '}\n')
_INSIDE = ('  %all-reduce.4 = f32[8,8]{1,0} all-reduce(%fusion.1), '
           'channel_id=33, replica_groups=[1,4]<=[4], to_apply=%add.1\n')


def test_loop_census_reads_a_body_and_what_it_calls():
    from tools.aot_analysis import loop_census

    assert loop_census(_CANNED_LOOPS.replace("{inside}", "")) == [
        {"body": "body.1", "matmuls": 1, "collectives": {}}]
    # the partitioner's sum of a carried gradient sits inside the body;
    # the one of ENTRY does not count
    assert loop_census(_CANNED_LOOPS.replace("{inside}", _INSIDE)) == [
        {"body": "body.1", "matmuls": 1, "collectives": {"all-reduce": 1}}]


@pytest.mark.parametrize("policy", ["dots", "nothing"])
def test_the_loss_is_one_local_loop_of_three_products(policy, remat_steps):
    """The mechanism's counter, on the program the chip runs: the fused
    cross-entropy is ONE ``while`` (the two-loop form had a forward loop of
    one product and a backward loop of three, the logits' a second time,
    with the partitioner's all-reduce of the head's gradient inside: eight
    a step at the cell's shapes); its body holds the three products a loss
    and its gradients need and no collective."""
    from tools.aot_analysis import loop_census

    loops = [row for row in loop_census(remat_steps[policy][4])
             if row["matmuls"]]
    assert len(loops) == 1, loops
    assert loops[0]["matmuls"] == 3, loops
    assert loops[0]["collectives"] == {}, loops


@pytest.mark.parametrize("policy", ["dots", "nothing"])
def test_the_heads_gradient_is_reduced_once_outside_the_loop(policy,
                                                             remat_steps):
    """One float32 reduce-scatter of the head's gradient onto the head's
    own sharding (vocab x embed / fsdp), an instruction of ``ENTRY``: what
    ``psum_scatter`` asks for after the loop. ``collectives_scheduled``
    counts it among the synchronous reduce-scatters (the cell's step: 57
    -> 58, where the two-loop form's eight all-reduces inside the backward
    loop were counted by nobody)."""
    hlo = remat_steps[policy][4]
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    heads = re.findall(
        r"= f32\[4096,128\]\S* reduce-scatter\([^\n]*shard_map/reduce_scatter",
        entry)
    assert len(heads) == 1, heads
    # the weights' own are all-reduce-scatter fusions: no other instruction
    # of the module is a reduce-scatter, in a loop or out of one
    assert len(re.findall(r"\sreduce-scatter\(", hlo)) == 1


# -- the kernels of the main path at Llama-3-8B widths ------------------------

_8B = llama.LlamaConfig.llama3_8b()
_H, _KV, _D = _8B.n_heads, _8B.n_kv_heads, _8B.head_dim


def _flash_fwd_bwd(t, one_chip, masked=False):
    from lzy_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, _H, t, _D), jnp.bfloat16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, t), jnp.bool_, sharding=one_chip)

    def loss(q, k, v, mask):
        out = flash_attention(q, k, v, causal=True, interpret=False,
                              kv_mask=mask if masked else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x, mask)


@pytest.mark.parametrize("t", [2048, "longest", "longest_masked"])
def test_flash_forward_and_backward_compile(t, one_chip):
    """T = 8192 (``LlamaConfig.max_seq_len``) used to fail in the backward:
    "Scoped allocation with size 21.00M and limit 16.00M". The longest
    length the wrapper accepts must compile too, with a ``kv_mask`` (whose
    bias slab stays resident beside K and V) and without, or the wrapper
    lies."""
    from lzy_tpu.ops.flash_attention import max_seq_len

    masked = t == "longest_masked"
    if not isinstance(t, int):
        t = max_seq_len(_D, jnp.bfloat16, masked)
        assert t >= _8B.max_seq_len
    compiled = _flash_fwd_bwd(t, one_chip, masked).compile()
    # forward, dQ, dK/dV: three Mosaic kernels, none interpreted
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_flash_refuses_a_length_it_cannot_serve_before_tracing(one_chip):
    from lzy_tpu.ops.flash_attention import max_seq_len

    too_long = max_seq_len(_D, jnp.bfloat16) + 128
    with pytest.raises(ValueError, match="VMEM.*longest accepted length"):
        _flash_fwd_bwd(too_long, one_chip)
    with pytest.raises(ValueError, match="divisible by 128"):
        _flash_fwd_bwd(2000, one_chip)


def test_llama_does_not_drop_to_the_reference_path_in_silence():
    """``use_flash_kernel=True`` at a length the kernel cannot take is an
    error, not the chunked reference (only init's dummy trace may differ)."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), use_flash_kernel=True)
    params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
    with pytest.raises(ValueError, match="divisible by 128"):
        jax.eval_shape(
            lambda p: llama.Llama(cfg).apply({"params": p},
                                             jnp.zeros((1, 100), jnp.int32)),
            params)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("t", [1, 5], ids=["decode", "verify"])
def test_paged_attention_auto_compiles(t, quantized, one_chip):
    from lzy_tpu.ops.paged_attention import KVQuant, paged_attention

    batch, page = 8, 16
    pages = _8B.max_seq_len // page
    n = batch * pages + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, page, _KV, _D), jnp.int8 if quantized else jnp.bfloat16)
    side = None
    if quantized:
        s = sds((n, page, _KV), jnp.float32)
        side = KVQuant(s, s, s, s)

    def read(q, k_pool, v_pool, table, pos, side):
        return paged_attention(q, k_pool, v_pool, table, pos,
                               kernel="pallas", dtype=jnp.bfloat16,
                               quant=side, interpret=False)

    jax.jit(read).lower(
        sds((batch, t, _H, _D), jnp.bfloat16), pool, pool,
        sds((batch, pages), jnp.int32), sds((batch, t), jnp.int32), side,
    ).compile()


@pytest.mark.parametrize("shape", ["8b_page16", "8b_page64", "benchmark"])
def test_pallas_paged_kernel_lowers_and_auto_is_it(shape, monkeypatch):
    """ROADMAP S2's kernel: the pool stays in HBM and pages are fetched by
    DMA, so the lowering has no pool block to refuse. It lowers at the 8B
    shapes with pages of 16 and 64 and at the benchmark's (32 slots, the 7
    GiB pool's 7168 pages, tables of 256 pages), ``"auto"`` is it on a
    TPU (and lax on this CPU), and an engine built for it outside the
    interpreter reports it: the lowering ran when the engine was built."""
    from lzy_tpu.ops import interpret
    from lzy_tpu.ops.paged_attention import (
        default_kernel, lower_pallas_for_tpu)
    from lzy_tpu.serving import PagedInferenceEngine

    assert default_kernel() == "lax"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_kernel() == "pallas"
    monkeypatch.undo()
    batch, n_blocks, page, pages = {
        "8b_page16": (8, 513, 16, _8B.max_seq_len // 16),
        "8b_page64": (8, 513, 64, _8B.max_seq_len // 64),
        "benchmark": (32, 7168, 16, 256),
    }[shape]
    lower_pallas_for_tpu(
        batch=batch, n_heads=_H, n_kv_heads=_KV, head_dim=_D,
        n_blocks=n_blocks, page_size=page, pages_per_seq=pages,
        dtype=jnp.bfloat16)
    cfg = llama.LlamaConfig.tiny()
    params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
    monkeypatch.setattr(interpret, "_process_wide", False)
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=page,
                                  kernel="pallas")
    try:
        assert engine.kernel_path == "pallas"
        assert engine.stats().kernel_path == "pallas"
    finally:
        engine.close()


def test_paged_decode_step_compiles_at_full_width(one_chip, monkeypatch):
    """One decode step of the engine ``chip_smoke.py`` serves, two layers
    deep, shapes only: parameters from ``jax.eval_shape``. The kernel is
    compiled, not interpreted, as on the chip."""
    from lzy_tpu.ops import interpret
    from lzy_tpu.serving import PagedInferenceEngine

    monkeypatch.setattr(interpret, "_process_wide", False)

    cfg = dataclasses.replace(_8B, n_layers=2, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda k: unbox(llama.init_params(cfg, k)[0]), jax.random.PRNGKey(0))
    slots = 2
    engine = PagedInferenceEngine(cfg, params, slots=slots, page_size=16,
                                  kernel="pallas")
    try:
        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
        compiled = engine._decode_step.lower(
            [on_chip(leaf) for leaf in engine._payload],
            jax.tree_util.tree_map(on_chip, params), vec, vec,
            jax.ShapeDtypeStruct((slots, cfg.max_seq_len // 16), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
            on_chip(engine._rng),
        ).compile()
    finally:
        engine.close()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # "auto" is the Pallas decode kernel: it is in this program, and the
    # gather of every row's whole table is not
    assert engine.kernel_path == "pallas"
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_prefill_step_compiles_at_full_width(one_chip, monkeypatch):
    """The round's one prefill program of the same engine at its widest
    chunk: the index leaves, the chunk cut out of the job's buffer and the
    first token's pick are inside it, so this is every device operation of
    a prefill round, compiled for the chip from shapes alone."""
    from lzy_tpu.ops import interpret
    from lzy_tpu.serving import PagedInferenceEngine

    monkeypatch.setattr(interpret, "_process_wide", False)

    cfg = dataclasses.replace(_8B, n_layers=2, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda k: unbox(llama.init_params(cfg, k)[0]), jax.random.PRNGKey(0))
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  kernel="pallas", prefill_budget=256,
                                  temperature=0.8, top_k=40, top_p=0.9)
    try:
        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        assert engine.prefill_chunk == 256
        compiled = engine.prefill.step.lower(
            [on_chip(leaf) for leaf in engine._payload], [],
            jax.ShapeDtypeStruct((1, engine.prefill.layout[-1]), jnp.int32,
                                 sharding=one_chip),
            jax.tree_util.tree_map(on_chip, params),
            on_chip(engine._rng), width=256).compile()
    finally:
        engine.close()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the chunk's attention read is the chunk kernel: no scores of the whole
    # table go through HBM
    assert "paged_chunk_attention" in compiled.as_text()


@pytest.mark.parametrize("batch,t", [(1, 16), (1, 256), (16, 9)])
@pytest.mark.parametrize("config", ["mistral", "nemotron", "solar"])
def test_chunk_read_compiles_at_the_three_configurations(
        config, batch, t, one_chip, monkeypatch):
    """The chunk kernel at the head shapes, pools and tables of the three
    benchmark configurations that read a pool ``[n_blocks, page, KV, D]``
    (32 / 8 over 7168 pages and a table of 256; 32 / 2 over 4096 and 256;
    64 / 8 over 4096 and 288), at the narrowest and widest prefill widths
    and at the verify window of ``spec_tokens=8`` over 16 slots (a q tile
    of 9 positions, no whole sublane tile).
    Mosaic takes the strided read of the float32 staging copy (of the
    bfloat16 buffer it does not: "Strided load with non 32-bit data"), and
    no copy of the pool is made. ``HeadPool.lower_read`` lowers it beside
    the decode kernel when an engine is built."""
    import importlib

    from lzy_tpu.models.serving import HeadPool
    from lzy_tpu.ops.paged_attention import paged_attention

    heads, kv, n, pages = {"mistral": (32, 8, 7168, 256),
                           "nemotron": (32, 2, 4096, 256),
                           "solar": (64, 8, 4096, 288)}[config]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, 16, kv, _D), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, table, pos: paged_attention(
        q, k, v, table, pos, kernel="pallas", interpret=False)).lower(
        sds((batch, t, heads, _D), jnp.bfloat16), pool, pool,
        sds((batch, pages), jnp.int32), sds((batch, t), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_chunk_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < n * 16 * kv * _D * 2 // 8

    class Shape(HeadPool):
        n_heads, n_kv_heads, head_dim, dtype = heads, kv, _D, jnp.bfloat16
        widest_prefill = 256

    lowered = []
    pa = importlib.import_module("lzy_tpu.ops.paged_attention")
    real = pa.lower_pallas_for_tpu
    monkeypatch.setattr(
        pa, "lower_pallas_for_tpu",
        lambda **kw: (lowered.append((kw["batch"], kw["t"])), real(**kw)))
    Shape().lower_read(slots=32, kv_blocks=n, page_size=16,
                       pages_per_seq=pages, kv_quant=None)
    assert lowered == [(32, 1), (1, 256)]
    lowered.clear()
    Shape().lower_read(slots=32, kv_blocks=n, page_size=16,
                       pages_per_seq=pages, kv_quant="int8")
    assert lowered == []


# -- the Nemotron-H serving kernels at published widths ----------------------

@pytest.mark.parametrize("rows", [64, 8, 256],
                         ids=["decode", "chunk8", "chunk256"])
def test_grouped_experts_compiles_at_published_widths(rows, one_chip):
    """128 held experts of 1024 x 2688 in bfloat16: a decode round's 64
    rows, the narrowest prefill chunk and the widest."""
    from lzy_tpu.ops import grouped_experts as gexp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jax.jit(lambda x, a, b, w: gexp.grouped_experts(
        x, a, b, w, interpret=False)).lower(
        sds((rows, 1024), jnp.bfloat16),
        sds((128, 1024, 2688), jnp.bfloat16),
        sds((128, 2688, 1024), jnp.bfloat16),
        sds((rows, 128), jnp.float32)).compile()


def test_ssm_state_update_compiles_at_published_widths(one_chip):
    """64 slots x 128 heads x 64 x 128 float32 of state, updated in place:
    the donated state is the output's buffer (no second 268 MB copy)."""
    from lzy_tpu.ops import mamba2

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda s, x, dt, a, b, c: mamba2.ssm_state_update(
            s, x, dt, a, b, c, interpret=False),
        donate_argnums=(0,)).lower(
        sds((64, 128, 64, 128)), sds((64, 128, 64)), sds((64, 128)),
        sds((128,)), sds((64, 8, 128)), sds((64, 8, 128))).compile()
    state_bytes = 64 * 128 * 64 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 8


# -- the Solar-Open2 serving kernels at published widths ---------------------

@pytest.mark.parametrize("rows", [32, 16, 256],
                         ids=["decode", "chunk16", "chunk256"])
def test_gated_experts_compile_at_published_widths(rows, one_chip):
    """40 held experts of three 4096 x 1280 matrices in bfloat16: tiles of
    4096 x 256 and, at 256 rows, a VMEM limit asked for by name (the rows'
    input and float32 result are 6 MB beside 12 MB of weight tiles)."""
    from lzy_tpu.ops import grouped_experts as gexp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((40, 4096, 1280), jnp.bfloat16)
    compiled = jax.jit(lambda x, g, a, b, w: gexp.grouped_experts(
        x, a, b, w, gate=g, interpret=False)).lower(
        sds((rows, 4096), jnp.bfloat16), up, up,
        sds((40, 1280, 4096), jnp.bfloat16),
        sds((rows, 40), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kda_state_update_compiles_at_published_widths(one_chip):
    """32 slots x 64 heads x 128 x 128 float32 of state, updated in place:
    the donated state is the output's buffer (no second 134 MB copy)."""
    from lzy_tpu.ops import kda

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vec = sds((32, 64, 128))
    compiled = jax.jit(
        lambda s, q, k, v, a, b, live: kda.kda_state_update(
            s, q, k, v, a, b, live, interpret=False),
        donate_argnums=(0,)).lower(
        sds((32, 64, 128, 128)), vec, vec, vec, vec, sds((32, 64)),
        sds((32,), jnp.bool_)).compile()
    state_bytes = 32 * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 8


@pytest.mark.parametrize("batch,t", [(32, 1), (32, 5), (1, 16), (1, 256)],
                         ids=["decode", "verify", "chunk16", "chunk256"])
def test_latent_reads_compile_at_published_widths(batch, t, one_chip):
    """The absorbed read of a paged latent cache under both its names: 16
    heads over a vector of 576 values in 640 lanes, a pool of 7,000 pages of
    16, a table of 512 pages a slot (64 KiB of scalar prefetch at 32 slots,
    the widest the repo compiles). No copy of the pool is made."""
    from lzy_tpu.ops import mla

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda q, pool, table, start: mla.mla_attention(
        q, pool, table, start, value_dim=512, scale=192 ** -0.5,
        kernel="pallas", interpret=False)).lower(
        sds((batch, t, 16, 640), jnp.bfloat16),
        sds((7000, 16, 640), jnp.bfloat16),
        sds((batch, 512), jnp.int32), sds((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("mla_paged_decode" if t <= 8 else "mla_paged_prefill") in text
    pool_bytes = 7000 * 16 * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("batch,t", [(16, 1), (1, 256)],
                         ids=["decode", "chunk256"])
def test_the_latent_choice_compiles_at_published_widths(batch, t, one_chip):
    """The exact choice of 2,048 among a table of 50,176 positions (392
    chunks of 128, four blocks of chunks): Mosaic takes the bisection's
    counts, the 0 / 1 products and the sublane roll; the whole program is
    the kernel and no sort."""
    from lzy_tpu.ops import latent_select as ls

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda scores, pos: ls.latent_topk(
        scores, pos, 2048, kernel="pallas", interpret=False)).lower(
        sds((batch, t, 50176), jnp.float32),
        sds((batch, t), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and " sort(" not in text
    assert ("latent_choice_decode" if t == 1
            else "latent_choice_prefill") in text


@pytest.mark.parametrize("batch,t", [(16, 1), (2, 8), (1, 256)],
                         ids=["decode", "verify", "chunk256"])
def test_the_latent_gather_compiles_at_published_widths(batch, t, one_chip):
    """The copy of the 2,048 chosen latent vectors a query out of a pool of
    12,545 pages of 64 through a table of 784: Mosaic takes the walk (page
    DMAs into blocks of 512 positions, the transpose that lays a tile's
    positions down a column, the 0 / 1 products) for a decode round and a
    verify window, both branches of the ``lax.cond`` are in the program,
    and a prefill chunk keeps XLA's gather alone."""
    from lzy_tpu.ops import latent_select as ls

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda pool, table, idx, n: ls.latent_gather(
        pool, table, idx, n, kernel="pallas", interpret=False)).lower(
        sds((12545, 64, 640), jnp.bfloat16), sds((batch, 784), jnp.int32),
        sds((batch, t, 2048), jnp.int32), sds((batch, t), jnp.int32)
    ).compile()
    text = compiled.as_text()
    if t > 8:
        assert "tpu_custom_call" not in text and "conditional" not in text
        return
    assert "latent_gather_decode" in text and "conditional(" in text
    # no copy of the pool on either branch
    pool_bytes = 12545 * 64 * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("rows", [32, 256], ids=["decode", "chunk256"])
def test_gated_experts_compile_at_a_width_of_eleven_lane_tiles(rows,
                                                               one_chip):
    """16 held experts of three 2048 x 1408 matrices: 1408 = 11 x 128 has no
    divisor in lanes but 128 and itself, so the tiles are 2048 x 128."""
    from lzy_tpu.ops import grouped_experts as gexp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((16, 2048, 1408), jnp.bfloat16)
    compiled = jax.jit(lambda x, g, a, b, w: gexp.grouped_experts(
        x, a, b, w, gate=g, interpret=False)).lower(
        sds((rows, 2048), jnp.bfloat16), up, up,
        sds((16, 1408, 2048), jnp.bfloat16),
        sds((rows, 16), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("batch,t", [(32, 1), (1, 16), (1, 256)],
                         ids=["decode", "chunk16", "chunk256"])
def test_group_reads_compile_at_published_widths(batch, t, window, one_chip):
    """The reads of pools ``[pages, page, KV x D]`` under both their names:
    128 query heads over 8 of 128 (a group of 16), pages of 32, a table of
    512 pages a slot (16,384 positions), with a window of 4096 and without.
    No copy of the pool is made (the ``[pages, page, KV, D]`` layout costs
    one a call: 287 MB a leaf here)."""
    import importlib

    pa = importlib.import_module("lzy_tpu.ops.paged_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((4385, 32, 1024), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, table, start: pa.paged_group_attention(
        q, k, v, table, start, window=window, kernel="pallas",
        interpret=False)).lower(
        sds((batch, t, 128, 128), jnp.bfloat16), pool, pool,
        sds((batch, 512), jnp.int32), sds((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("paged_group_decode" if t <= 8 else "paged_group_prefill") in text
    pool_bytes = 4385 * 32 * 1024 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("rows", [32, 256], ids=["decode", "chunk256"])
def test_gated_experts_compile_at_a_square_of_4096(rows, one_chip):
    """16 held experts of three 4096 x 4096 matrices (Command A+): tiles of
    4096 x 256 by bytes, a VMEM limit by name at 256 rows."""
    from lzy_tpu.ops import grouped_experts as gexp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((16, 4096, 4096), jnp.bfloat16)
    compiled = jax.jit(lambda x, g, a, b, w: gexp.grouped_experts(
        x, a, b, w, gate=g, interpret=False)).lower(
        sds((rows, 4096), jnp.bfloat16), up, up, up,
        sds((rows, 16), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the Jamba2-3B serving kernels at published widths -----------------------

@pytest.mark.parametrize("case", ["scan256", "scan16", "update", "read_decode",
                                  "read_chunk256"])
def test_jamba_kernels_compile_at_published_widths(case, one_chip):
    """The Mamba-1 scan of one row's chunk and the state update of 32 slots
    at 5120 channels x 16 state entries (the state ``[B, 16, 5120]`` float32,
    donated: updated in place, no second copy), and the attention read at 20
    query heads over ONE key-value head (a group of 20: no multiple of 8
    sublanes) over pools ``[16384, 128, 128]`` with a table of 512 pages a
    slot (65,536 positions)."""
    import importlib

    from lzy_tpu.ops import mamba1

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    di, n = 5120, 16
    if case.startswith("scan"):
        t = int(case[4:])
        compiled = jax.jit(
            lambda x, dt, a, b, c, s: mamba1.selective_scan(
                x, dt, a, b, c, s, interpret=False),
            donate_argnums=(5,)).lower(
            sds((1, t, di)), sds((1, t, di)), sds((n, di)), sds((1, t, n)),
            sds((1, t, n)), sds((1, n, di))).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "selective_scan" in text
        # nothing the size of the [T, N, Di] products is ever allocated
        assert compiled.memory_analysis().temp_size_in_bytes \
            < t * n * di * 4 // 4
    elif case == "update":
        compiled = jax.jit(
            lambda s, x, dt, a, b, c: mamba1.selective_state_update(
                s, x, dt, a, b, c, interpret=False),
            donate_argnums=(0,)).lower(
            sds((32, n, di)), sds((32, di)), sds((32, di)), sds((n, di)),
            sds((32, n)), sds((32, n))).compile()
        assert "selective_state_update" in compiled.as_text()
        state_bytes = 32 * n * di * 4
        assert compiled.memory_analysis().temp_size_in_bytes \
            < state_bytes // 8
    else:
        pa = importlib.import_module("lzy_tpu.ops.paged_attention")
        batch, t = (32, 1) if case == "read_decode" else (1, 256)
        pool = sds((16384, 128, 128), jnp.bfloat16)
        compiled = jax.jit(
            lambda q, k, v, table, start: pa.paged_group_attention(
                q, k, v, table, start, kernel="pallas",
                interpret=False)).lower(
            sds((batch, t, 20, 128), jnp.bfloat16), pool, pool,
            sds((batch, 512), jnp.int32), sds((batch,), jnp.int32)).compile()
        text = compiled.as_text()
        assert ("paged_group_decode" if t == 1
                else "paged_group_prefill") in text
        pool_bytes = 16384 * 128 * 128 * 2
        assert compiled.memory_analysis().temp_size_in_bytes \
            < pool_bytes // 8


@pytest.mark.parametrize("case", ["update64", "update1", "decode", "prefill"])
def test_zaya_kernels_compile_at_published_widths(case, one_chip,
                                                  monkeypatch):
    """ZAYA1-8B: the window update at 64 slots and at one row (a chunk of
    one), and the paged programs of a two-layer model at full width: the
    decode round of 64 slots (``cca_mix_update``, the decode read at 8 / 2
    heads of 128 over pages of 64, the expert product at 16 experts of 2048)
    and the prefill chunk of 256 (the chunk read, the expert product at 256
    rows). Mosaic refused the update once for a head's bias read as a lane
    slice of one row spread over the rows, which the lowering alone had let
    through."""
    from lzy_tpu.models import zaya
    from lzy_tpu.ops import cca
    from lzy_tpu.ops import interpret

    # the kernels as the chip compiles them, whatever conftest.py asked for
    monkeypatch.setattr(interpret, "_process_wide", False)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case.startswith("update"):
        b, (h, g, d) = int(case[6:]), (8, 2, 128)
        c, lk, width = (h + g) * d, g * d, cca.window_width(h, g, d)
        compiled = jax.jit(
            lambda win, new, v1, live, *w: cca.cca_mix_update(
                win, new, v1, live, cca.Mixer(*w), heads=h, groups=g,
                dtype=jnp.bfloat16, interpret=False),
            donate_argnums=(0,)).lower(
            sds((b, 2 * width)), sds((b, width)), sds((b, lk // 2)),
            sds((b,), jnp.bool_), sds((2, c)), sds((c,)),
            sds((h + g, 2 * d, d), jnp.bfloat16), sds((c,)), sds((g,))
        ).compile()
        assert "cca_mix_update" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
        return
    cfg = zaya.ZayaConfig(n_layers=2, max_seq_len=4096)
    page, batch, t = 64, *((64, 1) if case == "decode" else (1, 256))
    model = cfg.paged_model(page_size=page, kv_pages=2049, kernel="pallas",
                            kv_quant=None)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: zaya.init_params(cfg, jax.random.PRNGKey(0))))
    pages = cfg.max_seq_len // page
    cache = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        page_table=jnp.zeros((batch, pages), jnp.int32)))["cache"])

    def step(params, cache, toks, table, valid):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, page_table=table,
            valid_len=valid, mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1), upd["cache"], upd["stats"]

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((batch, t), jnp.int32),
        sds((batch, pages), jnp.int32), sds((batch,), jnp.int32)
    ).compile().as_text()
    for kernel in (("cca_mix_update", "paged_decode_attention")
                   if case == "decode" else ("paged_chunk_attention",)) \
            + ("grouped_experts",):
        assert kernel in text


# -- MiniCPM-SALA's serving kernels at the cell's shapes ----------------------

def _minicpm_sala_step(case, one_chip, monkeypatch):
    """MiniCPM-SALA at its published widths, a sparse layer and a lightning
    layer, as ``sparse-steady`` runs them (16 slots, pages of 64, a page
    table 528 wide, a pool of 3,855 pages), compiled for v5e: the decode
    round or the prefill chunk of 256. Returns ``(compiled, params)``."""
    from lzy_tpu.models import minicpm_sala as sala
    from lzy_tpu.ops import interpret

    # the kernels as the chip compiles them, whatever conftest.py asked for
    monkeypatch.setattr(interpret, "_process_wide", False)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = sala.MiniCPMSalaConfig(
        mixer_types=(sala.SPARSE, sala.LIGHTNING), max_seq_len=33792)
    page, batch, t = 64, *((16, 1) if case == "decode" else (1, 256))
    model = cfg.paged_model(page_size=page, kv_pages=3855, kernel="pallas",
                            kv_quant=None)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: sala.init_params(cfg, jax.random.PRNGKey(0))))
    pages = cfg.max_seq_len // page
    assert pages == 528
    cache = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        page_table=jnp.zeros((batch, pages), jnp.int32)))["cache"])

    def step(params, cache, toks, table, valid, told):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, page_table=table,
            valid_len=valid, mutable=["cache", "stats"],
            **({} if t == 1 else {"prompt_len": told}))
        return jnp.argmax(logits[:, -1], -1), upd["cache"], \
            upd.get("stats", {})

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((batch, t), jnp.int32),
        sds((batch, pages), jnp.int32), sds((batch,), jnp.int32),
        sds((batch,), jnp.int32)).compile()
    return compiled, params


@pytest.mark.parametrize("case", ["decode", "prefill"])
def test_minicpm_sala_kernels_compile_at_the_cells_shapes(case, one_chip,
                                                          monkeypatch,
                                                          capsys):
    """MiniCPM-SALA at its published widths, a sparse layer and a lightning
    layer, as ``sparse-steady`` runs them: 16 slots, pages of 64, a page
    table 528 wide (33,792 positions), a pool of 3,855 pages. The decode
    round (``sparse_select_decode``, the read of the chosen pages under the
    name ``sparse_decode_attention``, ``lightning_state_update`` at a state
    of 32 x 128 x 128 a slot) and the prefill chunk of 256
    (``sparse_select_prefill``, ``sparse_prefill_attention``, the shared
    scan). The compiled step's memory is printed."""
    compiled, params = _minicpm_sala_step(case, one_chip, monkeypatch)
    text = compiled.as_text()
    for kernel in (("sparse_select_decode", "sparse_decode_attention",
                    "lightning_state_update") if case == "decode"
                   else ("sparse_select_prefill",
                         "sparse_prefill_attention")):
        assert kernel in text
    assert "paged_decode_attention" not in text
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nminicpm_sala {case} step at the cell's shapes: arguments "
              f"{memory.argument_size_in_bytes:,} bytes, temporaries "
              f"{memory.temp_size_in_bytes:,} bytes")
    # the pool's leaves are donated and updated in place: no second copy,
    # and no scatter that makes the compiler copy a leaf into a layout of
    # its own and back (126 MB a leaf a layer a program: PERF.md section 6)
    pool_bytes = 3855 * 2 * 64 * 128 * 2
    assert memory.temp_size_in_bytes < 4 * pool_bytes
    assert not re.search(r"= bf16\[3855,2,64,128\]\S* copy\(", text)
    # nor a projection's weight transposed for its heads (four copies of
    # bf16[4096,4096] a layer pair before ``paged_blocks.into_heads``)
    assert _parameter_copies(text, params) == []


def test_minicpm_sala_decode_round_sorts_and_gathers_no_table(one_chip,
                                                              monkeypatch):
    """The decode round of a sparse layer packs its chosen pages by a
    running count (``ops/sparse_attention.py`` ``pack_chosen``): the
    compiled step at the cell's 16 slots, pages of 64 and 528 pages a row
    holds no ``sort`` instruction over the mask (``pred[16,2,528]``) and no
    gather whose result is the packed table, ``s32[16,2,528]`` or that
    flattened (``s32[16896]``). Until PR 68 it held one of each a sparse
    layer, a stable ``argsort`` of the mask and a ``take_along_axis`` by its
    order, 215 us a layer on the chip between the selector's result and
    the read's start. The one sort that stays is the lightning layer's, of
    its 16 rows' live flags (``ops/mamba2.py``: the update walks live rows
    first)."""
    compiled, _ = _minicpm_sala_step("decode", one_chip, monkeypatch)
    text = compiled.as_text()
    assert "sparse_decode_attention" in text
    assert [line.split("metadata=")[0]
            for line in re.findall(r"^.* sort\(.*$", text, re.M)
            if "528" in line.split(" sort(")[0]] == []
    assert not re.search(
        r"= s32\[(16,2,528|16896)\]\S* gather\(", text)


@pytest.mark.parametrize("case", ["kernel", "decode", "prefill"])
def test_brumby_programs_compile_at_the_cells_shapes(case, one_chip,
                                                     monkeypatch, capsys):
    """Brumby-14B-Base at its published widths as ``longgen-steady`` runs
    it: the two kernels alone (the update at 16 rows of 8 key-value heads of
    8,256 x 128: 65 tiles of 128, thirteen a grid step; the chunk scan at
    every width the engine builds for one row), and two layers of the
    model: the decode round of 16 slots (``power_retention_update``) and the
    prefill chunk of 256 (``power_retention_chunk``, two chunks a call).
    **A program holds one copy of the state**: every ``S`` and ``z`` is
    aliased to its output through the kernel, and the temporaries stay far
    under one layer's state (550 MB); ``phi`` of a prefill chunk (170 MB a
    layer before the kernel) is never written."""
    from lzy_tpu.models import brumby
    from lzy_tpu.ops import interpret
    from lzy_tpu.ops import power_retention as pr

    # the kernels as the chip compiles them, whatever conftest.py asked for
    monkeypatch.setattr(interpret, "_process_wide", False)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, h, kv, d = 16, 40, 8, 128
    s_shape, z_shape = pr.state_shapes(slots, kv, d)
    assert s_shape == (16, 8, 65, 128, 128) and z_shape == (16, 8, 5, 13,
                                                             128)
    assert pr.n_features(d) == 8256
    layer_state = 4 * (16 * 8 * 65 * 128 * 128 + 16 * 8 * 65 * 128)
    if case == "kernel":
        pr.lower_update_for_tpu(batch=slots, heads=h, kv_heads=kv,
                                head_dim=d, dtype=jnp.bfloat16)
        bf = jnp.bfloat16
        compiled = jax.jit(
            lambda s, z, q, k, v, g, live: pr.retention_state_update(
                s, z, q, k, v, g, live, interpret=False),
            donate_argnums=(0, 1)).lower(
            sds(s_shape), sds(z_shape), sds((slots, h, d), bf),
            sds((slots, kv, d), bf), sds((slots, kv, d), bf),
            sds((slots, kv)), sds((slots,), jnp.bool_)).compile()
        assert pr.UPDATE_KERNEL in compiled.as_text()
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes == layer_state
        assert memory.temp_size_in_bytes < 1 << 20
        for t in (8, 16, 32, 64, 128, 256):
            pr.lower_chunk_for_tpu(batch=1, t=t, heads=h, kv_heads=kv,
                                   head_dim=d, chunk=128, dtype=bf)
        one_s, one_z = pr.state_shapes(1, kv, d)
        compiled = jax.jit(
            lambda s, z, q, k, v, g, real: pr.retention_chunk_scan(
                q, k, v, g, s, z, real, interpret=False),
            donate_argnums=(0, 1)).lower(
            sds(one_s), sds(one_z), sds((1, 256, h, d), bf),
            sds((1, 256, kv, d), bf), sds((1, 256, kv, d), bf),
            sds((1, 256, kv)), sds((1, 256), jnp.bool_)).compile()
        assert pr.CHUNK_KERNEL in compiled.as_text()
        assert compiled.memory_analysis().alias_size_in_bytes \
            == layer_state // slots
        return
    cfg = brumby.BrumbyConfig(n_layers=2)
    batch, t = (slots, 1) if case == "decode" else (1, 256)
    model = cfg.paged_model(page_size=64, kv_pages=0, kernel="pallas",
                            kv_quant=None)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: brumby.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32)))["cache"])

    def step(params, cache, toks, valid):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, valid_len=valid,
            mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1), upd["cache"], \
            upd.get("stats", {})

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((batch, t), jnp.int32),
        sds((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    assert (pr.UPDATE_KERNEL in text) == (case == "decode")
    assert (pr.CHUNK_KERNEL in text) == (case == "prefill")
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nbrumby {case} step at the cell's shapes, two layers: "
              f"arguments {memory.argument_size_in_bytes:,} bytes, aliased "
              f"{memory.alias_size_in_bytes:,}, temporaries "
              f"{memory.temp_size_in_bytes:,} bytes")
    state = 2 * layer_state * batch // slots
    # both layers' S and z (and the two index leaves) come back in the
    # buffers they arrived in
    assert state <= memory.alias_size_in_bytes < state + 4096
    if case == "decode":
        assert memory.temp_size_in_bytes < layer_state // 4
    else:
        # the chunk's float32 logits and 2 MB: phi(q) of one chunk alone
        # (640 rows of 8,320 features a head) would be 170 MB a layer
        # (219 MB of temporaries before the kernel: PERF.md section 6)
        logits = t * cfg.vocab_size * 4
        assert memory.temp_size_in_bytes < logits + (8 << 20)
    assert _parameter_copies(text, params) == []


# -- Ouro's loop over the pass: one trace of the stack, no copy of the pool ----

@pytest.mark.parametrize("case", ["decode", "prefill"])
def test_ouro_steps_loop_over_the_pass_and_copy_no_pool(case, one_chip,
                                                        monkeypatch):
    """Ouro-2.6B at its published widths as ``looped-steady`` runs it, four
    layers of the 48 over the cell's pool leaves (``[384, 4, 16, 16, 128]``
    bfloat16: 48 MiB a leaf, a pass's share 12 MiB): the decode round of 16
    slots and the prefill chunk of 256. **The stack is compiled once and
    looped**: one ``while``, one attention kernel a layer (not one a (pass,
    layer)). **The pool is the loop's carry, written in place**: every leaf
    is aliased to its output, the temporaries stay under one leaf, and no
    ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` makes an array of
    a pass's share of a leaf or more (a scanned-over cache would slice and
    restack the pool every pass)."""
    from lzy_tpu.models import ouro
    from lzy_tpu.ops import interpret

    # the kernels as the chip compiles them, whatever conftest.py asked for
    monkeypatch.setattr(interpret, "_process_wide", False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype), tree)

    layers, page, kv_pages = 4, 16, 384
    cfg = ouro.OuroConfig(n_layers=layers, max_seq_len=4096)
    batch, t = (16, 1) if case == "decode" else (1, 256)
    model = cfg.paged_model(page_size=page, kv_pages=kv_pages,
                            kernel="pallas", kv_quant=None)
    params = on_chip(jax.eval_shape(
        lambda: ouro.init_params(cfg, jax.random.PRNGKey(0))))
    pages = cfg.max_seq_len // page
    cache = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        page_table=jnp.zeros((batch, pages), jnp.int32)))["cache"])

    def step(params, cache, toks, table, valid):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, page_table=table,
            valid_len=valid, mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1), upd["cache"], \
            upd.get("stats", {})

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((batch, t), jnp.int32),
        sds((batch, pages), jnp.int32), sds((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    kernel = "paged_decode_attention" if case == "decode" \
        else "paged_chunk_attention"
    assert text.count(" while(") == 1
    assert text.count("tpu_custom_call") == layers
    assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == layers
    leaf = kv_pages * cfg.total_ut_steps * page * 16 * 128
    pool_bytes = 2 * layers * leaf * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 2 * leaf            # one leaf's bytes
    moved = [(op, dims) for dims, op in re.findall(
        r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(",
        text)
        if np.prod([int(d) for d in dims.split(",")])
        >= leaf // cfg.total_ut_steps]
    assert moved == []
    assert _parameter_copies(text, params) == []


# -- no serving program copies a weight matrix --------------------------------

def _parameter_copies(text, params, least=1_000_000):
    """The ``copy(`` instructions of a compiled module whose result has the
    type and the shape of a parameter leaf of ``least`` elements or more, or
    of a matrix among them transposed (an ``[in, out]`` kernel laid out for
    the heads shows as ``[out, in]``): a weight the program lays out anew
    every time it runs. Read from the shapes alone: a chunk's activations of
    a weight's very shape would be counted too (none of the cells' are)."""
    names = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
    leaves = set()
    for leaf in jax.tree_util.tree_leaves(params):
        if leaf.size >= least:
            dtype = names.get(jnp.dtype(leaf.dtype).name, leaf.dtype.name)
            leaves.add((dtype, tuple(leaf.shape)))
            if leaf.ndim == 2:
                leaves.add((dtype, tuple(leaf.shape[::-1])))
    return [f"{dtype}[{dims}]" for dtype, dims in re.findall(
        r"= (\w+)\[([\d,]+)\]\S* copy\(", text)
        if (dtype, tuple(int(d) for d in dims.split(","))) in leaves]


def test_parameter_copies_reads_a_transposed_kernel():
    params = {"q_proj": {"kernel": jax.ShapeDtypeStruct(
        (4096, 8192), jnp.bfloat16)}, "norm": jax.ShapeDtypeStruct(
        (4096,), jnp.float32), "kv_b_proj": jax.ShapeDtypeStruct(
        (512, 16, 256), jnp.bfloat16)}
    text = """
  %copy.1 = bf16[8192,4096]{1,0:T(8,128)(2,1)} copy(%bitcast.3)
  %copy.2 = bf16[32,3,24576]{2,1,0} copy(%window)
  %copy.3 = f32[8192,4096]{1,0} copy(%other)
  %copy.4 = f32[4096]{0} copy(%scale)
  %copy.5 = bf16[4096,8192]{0,1} copy(%kernel)
  ROOT %copy.6 = bf16[256,16,512]{2,0,1} copy(%param_0.1794)
"""
    assert _parameter_copies(text, params) == ["bf16[8192,4096]",
                                               "bf16[4096,8192]"]


#: the four configurations that share ``PagedAttention`` or
#: ``LatentAttention``, as their cells run them: the program's model module,
#: its own configuration class and what the cell sets of it, the engine's
#: slots, the page size and the pool's pages (``benchmark/configs/*.json``;
#: nothing imported from there)
_SERVING_CELLS = {
    "solar-open2-serve-l8-ep8": (
        "solar_open2", "SolarOpen2Config", dict(
            vocab_size=24576, n_layers=8, attn_layers=(0, 4),
            experts_held=(0, 40), max_seq_len=4608), 32, 16, 4096),
    "moonlight-16b-a3b-serve-ep4": (
        "deepseek_v3", "DeepseekV3Config", dict(
            vocab_size=40960, experts_held=(0, 16), max_seq_len=8192),
        32, 16, 7767),
    "nemotron-3-super-serve-l11-ep4": (
        "nemotron_h", "NemotronHConfig", dict(
            vocab_size=32768, experts_held=(0, 128), max_seq_len=4096),
        64, 16, 16384),
    "jamba2-3b-serve": (
        "jamba", "JambaConfig", dict(max_seq_len=65536), 32, 128, 16384),
}


@pytest.mark.parametrize("case", ["decode", "prefill"])
@pytest.mark.parametrize("name", list(_SERVING_CELLS))
def test_serving_steps_copy_no_parameter(name, case, one_chip, monkeypatch):
    """The whole decode round and the whole prefill chunk of 256 of each
    configuration, every layer, at its cell's slots, page size and pool: the
    compiled program holds no ``copy`` of a parameter of a million elements
    or more. Before ``paged_blocks.into_heads`` the compiler transposed
    ``q_proj`` (and ``k_proj``, ``v_proj``) for the heads in every program:
    2 x bf16[8192,4096] and 4 x bf16[1024,4096] a Solar-Open2 round, 27 x
    bf16[2048,3072] a Moonlight round, bf16[4096,4096] and 2 x
    bf16[256,4096] a Nemotron-3-Super round, 2 x bf16[2560,2560] a Jamba2
    round (PERF.md section 6, PR 52)."""
    from lzy_tpu.ops import interpret

    # the kernels as the chip compiles them, whatever conftest.py asked for
    monkeypatch.setattr(interpret, "_process_wide", False)
    module, config, sets, slots, page, kv_pages = _SERVING_CELLS[name]
    module = importlib.import_module(f"lzy_tpu.models.{module}")
    cfg = getattr(module, config)(**sets)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype), tree)

    batch, t = (slots, 1) if case == "decode" else (1, 256)
    model = cfg.paged_model(page_size=page, kv_pages=kv_pages,
                            kernel="pallas", kv_quant=None)
    params = on_chip(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0))))
    pages = cfg.max_seq_len // page
    cache = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        page_table=jnp.zeros((batch, pages), jnp.int32)))["cache"])

    def step(params, cache, toks, table, valid):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, page_table=table,
            valid_len=valid, mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1), upd["cache"], \
            upd.get("stats", {})

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((batch, t), jnp.int32),
        sds((batch, pages), jnp.int32), sds((batch,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert _parameter_copies(text, params) == []


@pytest.mark.parametrize("rows", [8, 64, 256])
def test_polynorm_experts_compile_at_published_widths(rows, one_chip):
    """The dropless product under PolyNorm at Motif-3-Beta's 4096 x 1280, 48
    experts held, a decode round's rows and the widest chunk's: two phases
    an expert over five tiles of 256, the gate's product kept in a float32
    scratch a row; at 256 rows the kernel asks for its VMEM by name."""
    from lzy_tpu.ops import polynorm_experts as pne

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((48, 4096, 1280), jnp.bfloat16)
    text = jax.jit(lambda x, g, u, d, p, w: pne.polynorm_experts(
        x, g, u, d, p, w, scale=0.5, clamp=0.5, kernel="pallas",
        interpret=False)).lower(
        sds((rows, 4096), jnp.bfloat16), up, up,
        sds((48, 1280, 4096), jnp.bfloat16), sds((48, 4), jnp.float32),
        sds((rows, 48), jnp.float32)).compile().as_text()
    assert "tpu_custom_call" in text and "polynorm_experts" in text


@pytest.mark.parametrize("rows", [40, 64, 256])
def test_the_stream_mix_compiles_at_published_widths(rows, one_chip):
    """``mhc_pre`` and ``mhc_post`` at four float32 streams of 4096: the
    projection with a token a lane, twenty sweeps, the transposition of the
    mix and the mixing, a tile of 64 rows a grid step; the new streams take
    the old ones' place."""
    from lzy_tpu.ops import mhc

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, phi, alpha, b, y):
        h, mix = mhc.mhc_pre(x, phi, alpha, b, streams=4, sweeps=20,
                             kernel="pallas", interpret=False)
        return h, mhc.mhc_post(x, y, mix, streams=4, kernel="pallas",
                               interpret=False)

    text = jax.jit(both, donate_argnums=(0,)).lower(
        sds((rows, 16384), jnp.float32), sds((24, 16384), jnp.float32),
        sds((3,), jnp.float32), sds((24,), jnp.float32),
        sds((rows, 4096), jnp.bfloat16)).compile().as_text()
    assert "mhc_pre" in text and "mhc_post" in text
    # in place: the program copies no stream
    assert not re.search(r"\[\d+,16384\][^ ]* copy\(", text)


@pytest.mark.parametrize("batch,t,heads", [(64, 1, 80), (1, 256, 20)],
                         ids=["decode", "chunk256"])
def test_latent_reads_compile_at_eighty_heads(batch, t, heads, one_chip):
    """``ops/mla.py``'s read as it is at Motif-3-Beta's heads: all 80 in a
    decode round of 64 slots (four slots a grid cell), 20 a call in a chunk
    of 256 (a tile of 64 positions x 40 heads and more does not fit a core's
    VMEM: ``MotifConfig.prefill_read_heads``); a pool of 12,289 pages of 64,
    a table of 192."""
    from lzy_tpu.models.motif import MotifConfig
    from lzy_tpu.ops import mla

    assert MotifConfig().prefill_read_heads == 20

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda q, pool, table, start: mla.mla_attention(
        q, pool, table, start, value_dim=512, scale=192 ** -0.5,
        kernel="pallas", interpret=False)).lower(
        sds((batch, t, heads, 640), jnp.bfloat16),
        sds((12289, 64, 640), jnp.bfloat16),
        sds((batch, 192), jnp.int32), sds((batch,), jnp.int32)
    ).compile().as_text()
    assert ("mla_paged_decode" if t <= 8 else "mla_paged_prefill") in text


@pytest.mark.parametrize("batch,heads,width,value,window,blocks,pages", [
    (16, 64, 1152, 1024, 513, 225, 784), (64, 80, 640, 512, 128, 449, 192)],
    ids=["dots3", "motif"])
def test_the_latent_window_read_compiles_at_both_cells_shapes(
        batch, heads, width, value, window, blocks, pages, one_chip):
    """``latent_window_decode`` (``ops/mla.py``'s kernel begun at the
    window's first page) at ``indexed-steady``'s decode round (16 slots, 64
    heads over 1,152 lanes under a window of 513: two blocks of five pages
    of 64) and at ``hyper-steady``'s (64 slots, 80 heads over 640 lanes
    under 128: one block of three): Mosaic takes the blocks and the scratch
    fits a core's VMEM."""
    from lzy_tpu.ops import latent_select

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda q, pool, table, start:
                   latent_select.latent_window_attention(
                       q, pool, table, start, window=window, value_dim=value,
                       scale=192 ** -0.5, kernel="pallas",
                       interpret=False)).lower(
        sds((batch, 1, heads, width), jnp.bfloat16),
        sds((blocks, 64, width), jnp.bfloat16),
        sds((batch, pages), jnp.int32), sds((batch,), jnp.int32)
    ).compile().as_text()
    assert "latent_window_decode" in text and "mla_paged" not in text


# -- LongCat-Flash's two kernels at its widths (the benchmark's cut) ----------

@pytest.mark.parametrize("rows", [128, 256], ids=["decode", "chunk256"])
def test_gated_experts_compile_at_a_hidden_width_of_6144(rows, one_chip):
    """16 held experts of three 6144 x 2048 matrices (LongCat-Flash): weight
    tiles of 6144 x 128 lanes, the narrowest ``_tile`` gives (a tile of 256
    lanes is 3 MB, over ``_TILE_BYTES``); the rows' input and float32 result
    of 256 rows ask for VMEM by name."""
    from lzy_tpu.ops import grouped_experts as gexp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert gexp._tile(2048, 6144, 2) == 128
    up = sds((16, 6144, 2048), jnp.bfloat16)
    compiled = jax.jit(lambda x, g, a, b, w: gexp.grouped_experts(
        x, a, b, w, gate=g, interpret=False)).lower(
        sds((rows, 6144), jnp.bfloat16), up, up,
        sds((16, 2048, 6144), jnp.bfloat16),
        sds((rows, 16), jnp.float32)).compile()
    assert "grouped_experts" in compiled.as_text()


@pytest.mark.parametrize("batch,t,heads", [(128, 1, 64), (1, 256, 16)],
                         ids=["decode", "chunk256"])
def test_latent_reads_compile_at_sixty_four_heads(batch, t, heads, one_chip):
    """``ops/mla.py``'s read as it is at LongCat-Flash's heads: all 64 in a
    decode round of 128 slots (8 slots a grid cell under ``_CELL_ROWS``), 16
    a call in a chunk of 256 (``LongcatFlashConfig.prefill_read_heads``: a
    tile of 64 positions x 64 heads does not fit a core's VMEM); a pool of
    4,096 pages of 64, a table of 128."""
    from lzy_tpu.models.longcat_flash import LongcatFlashConfig
    from lzy_tpu.ops import mla

    assert LongcatFlashConfig().prefill_read_heads == 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda q, pool, table, start: mla.mla_attention(
        q, pool, table, start, value_dim=512, scale=192 ** -0.5,
        kernel="pallas", interpret=False)).lower(
        sds((batch, t, heads, 640), jnp.bfloat16),
        sds((4096, 64, 640), jnp.bfloat16),
        sds((batch, 128), jnp.int32), sds((batch,), jnp.int32)
    ).compile().as_text()
    assert ("mla_paged_decode" if t <= 8 else "mla_paged_prefill") in text
