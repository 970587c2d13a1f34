"""The program names its device work (``lzy_tpu/utils/trace.py`` ``part``):
in every served family's decode, prefill and verify programs and in the train
step, each operation that does work (a product, a convolution, a custom call,
a gather, a scatter, a sort, a loop) carries exactly one ``part.<name>`` of
the vocabulary in its ``op_name``, which is what a device trace's ``tf_op``
holds and ``benchmark/readers/part_share.py`` files device time by. The
programs are the engine's own jitted steps at the tiny sizes the families'
own tests build, compiled for the CPU; nothing runs. A loop may instead be a
container (Ouro's pass, a train step's accumulation): no part of its own and
a body whose operations carry theirs. The file's name sorts last on purpose
(``tests/test_zz_deepseek_v3.py`` says why)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils import trace

#: module under ``lzy_tpu.models`` -> the engine's keywords, as the family's
#: own test file builds it. ``kernel="lax"``: an op's part sits on its public
#: entry point, in front of the choice of kernel, and an interpreted Pallas
#: kernel is thousands of instructions that say nothing more
FAMILIES = {
    "llama": dict(page_size=16, spec_tokens=2),
    "nemotron_h": dict(page_size=16),
    "solar_open2": dict(page_size=16),
    "deepseek_v3": dict(page_size=16),
    "cohere2_moe": dict(page_size=4),
    "jamba": dict(page_size=8),
    "zaya": dict(page_size=8),
    "minicpm_sala": dict(page_size=16),
    "brumby": dict(page_size=16),
    "ouro": dict(page_size=8),
    "dots3_note": dict(page_size=4),
    "motif": dict(page_size=4),
    "longcat_flash": dict(page_size=4),
}

#: the instructions that must carry a part
WORK = ("dot", "convolution", "custom-call", "gather", "scatter", "sort",
        "while")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = .*?[\]})] (?P<kind>[\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>[\w.\-]+) \(.*\{$")
_BODY = re.compile(r"body=%([\w.\-]+)")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")


@pytest.fixture(scope="module", autouse=True)
def fresh_compiles():
    """The persistent compile cache keys a program without its metadata, so
    a hit hands back the text of whichever build came first, under that
    build's ``op_name``s: these compiles go round it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


_PART = re.compile(r"(?:^|[/(])" + re.escape(trace.PART_PREFIX)
                   + r"([A-Za-z0-9_]+)")


def parts_of(op_name: str) -> list:
    """The parts an ``op_name`` holds, forward (``.../part.proj/...``) or
    inside a transform's wrapper (``transpose(jvp(part.proj))``)."""
    return _PART.findall(op_name)


def instructions(text: str):
    """``[(computation, name, kind, op_name, line)]`` of a compiled module's
    text."""
    out, inside = [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group("name")
            continue
        m = _INSTRUCTION.match(line)
        if m and inside:
            named = _OP_NAME.search(line)
            out.append((inside, m.group("name"), m.group("kind"),
                        named.group(1) if named else "", line))
    return out


def unnamed_work(text: str) -> list:
    """The instructions of ``WORK`` whose ``op_name`` does not hold exactly
    one part of the vocabulary."""
    found = instructions(text)
    by_computation: dict = {}
    calls: dict = {}
    for comp, _, _, op_name, line in found:
        by_computation.setdefault(comp, []).append(op_name)
        calls.setdefault(comp, set()).update(_CALLS.findall(line))

    def holds_parts(comp, seen=()):
        if comp in seen:
            return False
        return any(parts_of(n) for n in by_computation.get(comp, ())) or any(
            holds_parts(c, seen + (comp,)) for c in calls.get(comp, ()))

    bad = []
    for comp, name, kind, op_name, line in found:
        if kind not in WORK or not op_name:
            # with no op_name at all an instruction is the compiler's own
            # (the CPU's dot decomposition drops it): JAX names every one
            continue
        mine = parts_of(op_name)
        if len(mine) == 1 and mine[0] in trace.PARTS:
            continue
        body = _BODY.search(line)
        if kind == "while" and not mine and body \
                and holds_parts(body.group(1)):
            continue                    # a container: its body is named
        bad.append((kind, name, op_name))
    return bad


def _tiny(family):
    module = importlib.import_module(f"lzy_tpu.models.{family}")
    config = next(v for k, v in vars(module).items()
                  if k.endswith("Config") and hasattr(v, "tiny")
                  and v.__module__ == module.__name__)
    # (Motif's twenty Sinkhorn sweeps are twenty copies of one loop body)
    cfg = config.tiny(sweeps=2) if family == "motif" else config.tiny()
    params = module.init_params(cfg, jax.random.PRNGKey(1))
    if family == "llama":
        from lzy_tpu.models.common import unbox

        params = unbox(params[0])
    return cfg, params


def engine_programs(engine) -> dict:
    """``{name: lowered}``: the engine's decode step, one prefill width and
    (with speculation on) the verify step, lowered from the shapes
    ``warmup()`` and a prefill round's dispatch hand them."""
    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    payload = [aval(leaf) for leaf in engine._payload]
    vec = jax.ShapeDtypeStruct((engine.slots,), jnp.int32)
    mask = jax.ShapeDtypeStruct((engine.slots,), jnp.bool_)
    rng = aval(engine._rng)
    table = jax.ShapeDtypeStruct((engine.slots, engine._pages_per_seq),
                                 jnp.int32)
    pt = table if engine._pooled else vec
    if engine._win is not None:
        pt = (pt, pt)
    out = {"decode": engine._decode_step.lower(
        payload, engine.params, vec, vec, pt, mask, rng)}
    if engine.spec_tokens > 0:
        prop = jax.ShapeDtypeStruct((engine.slots, engine.spec_tokens),
                                    jnp.int32)
        out["verify"] = engine._verify_step.lower(
            payload, engine.params, vec, prop, vec, vec, pt, mask, rng)
    pre = engine.prefill
    rows = [jax.ShapeDtypeStruct((1,) + payload[i].shape[1:],
                                 payload[i].dtype) for i in pre._state_at]
    job = jax.ShapeDtypeStruct((1, pre.layout[3]), jnp.int32)
    second = () if engine._win is None else (
        jax.ShapeDtypeStruct((1, engine._pages_per_seq), jnp.int32),)
    out["prefill"] = pre.step.lower(
        [payload[i] for i in pre._pool_at], rows, job, engine.params, rng,
        *second, width=engine.prefill_chunk)
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_served_program_names_its_work(family):
    cfg, params = _tiny(family)
    engine = PagedInferenceEngine(cfg, params, slots=2, prefill_chunk=16,
                                  kernel="lax", **FAMILIES[family])
    try:
        programs = engine_programs(engine)
    finally:
        engine.close()
    assert set(programs) >= {"decode", "prefill"}
    for name, lowered in programs.items():
        bad = unnamed_work(lowered.compile().as_text())
        assert not bad, (family, name, bad[:8])


def test_the_train_step_names_its_work():
    """The benchmark's step in small: rematerialised layers, the fused loss
    (a ``custom_vjp`` whose backward is traced apart), AdamW, two devices
    under fsdp. The backward carries the forward's parts under
    ``transpose(jvp(...))``."""
    import dataclasses

    import optax

    from lzy_tpu.models import llama, unbox
    from lzy_tpu.parallel import TrainState, make_train_step, mesh_for

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                              remat=True, fused_ce=True)
    boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = mesh_for(2, fsdp=2)
    tx = optax.adamw(1e-3)
    step, shard_state, _ = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=axes)
    state = shard_state(TrainState.create(unbox(boxed), tx))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    text = step.lower(state, batch).compile().as_text()
    bad = unnamed_work(text)
    assert not bad, bad[:8]
    named = [n for _, _, _, n, _ in instructions(text) if parts_of(n)]
    assert {trace.PROJ, trace.ATTN_READ, trace.FFN, trace.LOSS,
            trace.OPTIMIZER} <= {p for n in named for p in parts_of(n)} \
        <= trace.PARTS
    assert any("transpose(jvp(" in n for n in named)


def test_a_part_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="no device part"):
        trace.part("layer_3")
    with pytest.raises(ValueError, match="no device part"):
        trace.part("Proj")
    assert all(re.fullmatch(r"[a-z0-9_]+", p) for p in trace.PARTS)
    assert len(trace.PARTS) <= 24


def test_parts_do_not_nest():
    def f(x):
        with trace.part(trace.FFN):
            with trace.part(trace.NORM):
                return x @ x

    text = jax.jit(f).lower(jnp.ones((4, 4))).compile().as_text()
    (op_name,) = [n for _, _, kind, n, _ in instructions(text)
                  if kind == "dot"]
    assert parts_of(op_name) == [trace.FFN]
    # and the thread's open part is closed again: a sibling names itself
    with trace.part(trace.NORM):
        assert trace._tls.part == trace.NORM
    assert trace._tls.part is None
