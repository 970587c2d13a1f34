"""The engine's KV I/O object (``serving/kv_io.py``): the four movers held
to one contract, and the hand-off to the scheduling thread.

- **One contract for demotion, promotion, export and import**: a block's
  payload whose leaf set, shape or dtype does not fit the pool it is
  offered to is refused, and the refusal leaves that pool's bytes and its
  tree's refcounts as they were. A sink (promotion, import) is offered a
  spoiled payload; a source (demotion, export) offers what it gathered to
  a pool that was built otherwise.
- **One hand-off**: with no loop thread a call runs inline; with one it
  runs on the thread that services the object, every queued call behind
  ONE ``io`` drain and in arrival order; a timeout gives the caller's
  default; ``close()`` releases every waiter.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lzy_tpu.channels.kv_transfer import KVBlockExport
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine, RadixCache
from lzy_tpu.serving.kv_io import KvIO
from lzy_tpu.serving.kv_tier import HostKVTier
from lzy_tpu.utils.clock import SYSTEM_CLOCK

PAGE = 8
DEFECTS = ("leaves", "shape", "dtype")
#: three whole blocks and a token to prefill from
PROMPT = list(range(1, 3 * PAGE + 1)) + [3]
PREFIX = PROMPT[:3 * PAGE]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _engine(tiny_model, defect=None, **kw):
    """An engine over the tiny model's pool; ``defect`` builds the pool
    otherwise (it is never run: the weights need not fit it)."""
    cfg, params = tiny_model
    if defect == "leaves":
        kw["kv_quant"] = "int8"         # codes, scales and zero points
    elif defect == "shape":
        cfg = dataclasses.replace(cfg, n_kv_heads=1)
    elif defect == "dtype":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    kw.setdefault("kv_host_tier_bytes", 1 << 20)
    return PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                kv_blocks=7, **kw)


def _run(engine, prompt):
    req = engine.submit(prompt, max_new_tokens=2)
    for _ in range(200):
        engine.step()
        if req.done:
            break
    assert req.done and req.error is None, req.error


@pytest.fixture(scope="module")
def gathered(tiny_model):
    """What the sources hand out: ``export`` is the export mover's gather
    of the prompt's three blocks, ``tier`` a host tier that the demotion
    mover filled when a second prompt evicted them."""
    tier = HostKVTier(1 << 20, PAGE)
    source = _engine(tiny_model, kv_host_tier_bytes=None, kv_tier=tier)
    _run(source, PROMPT)
    export = source.kv_io.export_kv(PREFIX)
    assert export.n_blocks == 3 and export.tokens == PREFIX
    # six blocks of another prompt: the pool's every usable block, so the
    # three cached ones go in ONE eviction round
    _run(source, [(11 * i) % 60 + 1 for i in range(5 * PAGE)] + [9])
    assert source.kv_io.gather_rounds == 1
    assert [tier.has(c) for c in _chains()] == ["host"] * 3
    return export, tier


def _spoiled(leaves, defect):
    """``leaves`` with one of them gone, narrower or of another dtype."""
    out, key = dict(leaves), sorted(leaves)[0]
    if defect == "leaves":
        del out[key]
    elif defect == "shape":
        out[key] = out[key][..., :-1]
    else:
        out[key] = out[key].astype(np.float32)
    return out


def _state(engine):
    """The pool's bytes and the tree's bookkeeping, to compare."""
    kv = engine.kv
    return ([np.asarray(leaf).tobytes() for leaf in engine._payload],
            list(kv.pool._ref), kv.pool.free_count(), sorted(kv._node_of),
            kv.available())


def _chains():
    return [tuple(PREFIX[:(i + 1) * PAGE]) for i in range(3)]


@pytest.mark.parametrize("defect", DEFECTS)
@pytest.mark.parametrize("mover", ["demote", "promote", "export", "import"])
def test_a_payload_that_does_not_fit_the_pool_is_refused(
        tiny_model, gathered, mover, defect):
    export, demoted = gathered
    if mover == "demote":
        # what demotion gathered, offered to a pool built otherwise
        pool = _engine(tiny_model, defect, kv_host_tier_bytes=None,
                       kv_tier=demoted)
        before = _state(pool)
        assert pool.kv_io.promote(PREFIX) == 0
        # re-filed: the payload never logically left the tier
        assert [demoted.has(c) for c in _chains()] == ["host"] * 3
        assert demoted.stats()["promotions"] == 0
    elif mover == "promote":
        # a spoiled payload in the pool's own tier
        pool = _engine(tiny_model)
        bad = _spoiled(export.leaves, defect)
        for i, chain in enumerate(_chains()):
            pool.kv_tier.put(chain, {k: v[i] for k, v in bad.items()})
        before = _state(pool)
        assert pool.kv_io.promote(PREFIX) == 0
        assert [pool.kv_tier.has(c) for c in _chains()] == ["host"] * 3
        assert pool.kv_tier_promotions == 0
    elif mover == "export":
        # what export gathered, offered to a pool built otherwise
        pool = _engine(tiny_model, defect)
        before = _state(pool)
        assert pool.kv_io.import_kv(export) == 0
    else:
        # a spoiled payload offered to a pool like the one it came from
        pool = _engine(tiny_model)
        before = _state(pool)
        assert pool.kv_io.import_kv(KVBlockExport(
            tokens=PREFIX, page_size=PAGE,
            leaves=_spoiled(export.leaves, defect))) == 0
    assert _state(pool) == before
    assert pool.kv.match_len(PREFIX) == 0
    if mover in ("promote", "import"):
        # and the payload as it was gathered is taken, by the same check
        assert pool.kv_io.import_kv(export) == 3
        assert pool.kv.match_len(PREFIX) == 3 * PAGE


# -- the hand-off ---------------------------------------------------------------

CHAIN = list(range(2 * PAGE))


def _bare(threaded, drains):
    """A ``KvIO`` over a tree that holds ``CHAIN`` and a pool with no
    leaves, its scheduler's side played by the test."""
    kv = RadixCache(8, PAGE)
    blocks = kv.allocate(2)
    kv.insert(CHAIN, blocks)
    kv.release(blocks)
    return KvIO(kv, PAGE, SYSTEM_CLOCK, leaf_keys=[], refusal=None,
                payload=list, adopt=lambda leaves: None,
                drain=drains.append, wake=lambda: None,
                threaded=lambda: threaded, closed=lambda: False,
                note_import=lambda outcome, blocks: None)


def _ask(io, results, name, call):
    """``call`` from a thread of its own, once the calls asked before it
    are queued."""
    queued = len(io._calls)
    thread = threading.Thread(
        target=lambda: results.__setitem__(name, call()), daemon=True)
    thread.start()
    deadline = time.monotonic() + 5.0
    while len(io._calls) == queued and time.monotonic() < deadline:
        time.sleep(0.001)
    assert len(io._calls) == queued + 1
    return thread


@pytest.mark.parametrize("case", ["inline", "loop", "timeout", "close"])
def test_the_hand_off_to_the_scheduling_thread(case):
    drains, results = [], {}
    me = threading.get_ident()
    if case == "inline":
        io = _bare(False, drains)
        assert io._on_scheduler("probe", threading.get_ident, None,
                                1.0) == me
        assert io.park_chain("conv", CHAIN) is True
        assert io.stats()["kv_parked_blocks"] == 2
        assert io.request_kv_export(CHAIN).tokens == CHAIN
        assert io.unpark_chain("conv") is True
        assert io._on_scheduler("probe", lambda: 1 // 0, "dflt",
                                1.0) == "dflt"
        assert drains == [] and not io._calls
    elif case == "loop":
        io = _bare(True, drains)
        threads = [
            _ask(io, results, "park", lambda: io.park_chain("conv", CHAIN)),
            _ask(io, results, "probe", lambda: io._on_scheduler(
                "probe", threading.get_ident, None, 5.0)),
            _ask(io, results, "export", lambda: io.request_kv_export(CHAIN)),
            _ask(io, results, "broken", lambda: io._on_scheduler(
                "probe", lambda: 1 // 0, "dflt", 5.0)),
            _ask(io, results, "unpark", lambda: io.unpark_chain("conv")),
        ]
        assert drains == [] and results == {}
        assert io.service() is True
        for thread in threads:
            thread.join(5.0)
        # on the servicing thread, behind one drain, in arrival order (an
        # unpark run before its park would have found nothing)
        assert drains == ["io"]
        assert results["probe"] == me
        assert results["park"] is True and results["unpark"] is True
        assert results["export"].tokens == CHAIN
        assert results["broken"] == "dflt"
        assert io.stats()["kv_parked_chains"] == 0
        # nothing queued: no drain
        assert io.service() is False and drains == ["io"]
    elif case == "timeout":
        io, ran = _bare(True, drains), []
        assert io._on_scheduler("probe", lambda: ran.append(1), "dflt",
                                0.01) == "dflt"
        assert io.park_chain("conv", CHAIN, timeout_s=0.01) is False
        assert io.request_kv_export(CHAIN, timeout_s=0.01) is None
        assert ran == [] and drains == []
    else:
        io, ran = _bare(True, drains), []
        threads = [
            _ask(io, results, "park",
                 lambda: io.park_chain("conv", CHAIN, timeout_s=30.0)),
            _ask(io, results, "export",
                 lambda: io.request_kv_export(CHAIN, timeout_s=30.0)),
            _ask(io, results, "probe", lambda: io._on_scheduler(
                "probe", lambda: ran.append(1), "dflt", 30.0)),
        ]
        t0 = time.monotonic()
        io.close()
        for thread in threads:
            thread.join(5.0)
        assert time.monotonic() - t0 < 5.0
        assert results == {"park": False, "export": None, "probe": "dflt"}
        assert ran == [] and drains == [] and not io._calls
