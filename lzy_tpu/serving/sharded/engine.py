"""A gang replica: the paged engine's forwards tensor-sharded over a mesh.

``ShardedPagedInferenceEngine`` subclasses ``PagedInferenceEngine`` and
changes *only* where arrays live and how the jitted programs partition —
the scheduler (one fence per round, overlap-window admission, WFQ,
chunked prefill, speculation) is inherited verbatim. The contract:

* **Bit-identity.** Under the ``partition.SERVE_RULES`` placement no
  float reduction ever changes operand order versus the single-device
  engine (only non-contraction dims shard; see ``partition`` module
  docstring), so greedy output, sampled rng draw order, and spec
  accept/reject decisions are identical on a 1×N mesh. Exact under f32
  compute; under bf16 compute the partitioned program's different XLA
  fusion boundaries round intermediates at different points (1-ULP logit
  noise — scheme-independent, any graph change does it), so bf16 streams
  are pinned by fixed-seed tests rather than guaranteed against argmax
  near-ties. Pinned by ``tests/test_sharded_serving.py``.
* **One fence per round.** The emit matrix (next-token ids / packed spec
  acceptances) is replicated by the ``act_vocab`` anchor before it leaves
  the jit, so the inherited ``_fetch`` is still exactly one device→host
  sync per steady-state decode round (``host_fetches`` contract): taken
  at once by ``step()``, one turn late by the loop thread, which keeps
  one round in flight as the base engine does (round n+1's inputs are
  round n's replicated outputs; the rebuild from the host mirrors after
  an admission follows a drain).
* **Sharded pool, shared table.** KV pool payload leaves shard on the
  kv_heads axis; the logical block table (``_tables``/``RadixCache``) is
  host-side and shared — one admission/eviction decision drives N
  shard-local scatter/gather paths. Per-shard occupancy is symmetric by
  construction (the ``lzy_sharded_shard_skew`` gauge exists to catch a
  future per-shard allocator drifting from this invariant).
* **Gang failure.** One dead host is engine-fatal for the whole gang:
  ``mark_host_dead`` poisons ``step()`` with ``GangHostDead``, the
  inherited loop-death handler fails every outstanding request with
  ``"engine loop died"`` — exactly the error prefix the gateway's
  failover path resubmits with fenced tokens — and health/fleet retire
  the replica whole. There is no partial-gang mode.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lzy_tpu.models.generate import init_cache
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving.engine import PREFILL_CALLS, PagedInferenceEngine
from lzy_tpu.serving.sharded import metrics as _m
from lzy_tpu.serving.sharded.partition import (
    SERVE_RULES, pool_leaf_sharding, serve_mesh_for, shard_params)


class NoPartitionRules(ValueError):
    """The sharded engine was given a model family it has no partition
    rules for."""


class GangHostDead(RuntimeError):
    """A shard host of a gang replica died; the whole gang is down."""


class ShardedPagedInferenceEngine(PagedInferenceEngine):
    """Paged engine whose prefill/decode/verify run SPMD over a mesh.

    ``mesh`` is a prebuilt ``jax.sharding.Mesh`` (must carry a ``tp``
    axis) or None to build a 1×``tp`` mesh over the first ``tp`` local
    devices. All other kwargs are the ``PagedInferenceEngine`` surface,
    unchanged — the gateway, streams, tenancy, and chaos layers cannot
    tell a gang from a single-device replica except through
    ``gang_size``/``kv_mesh_shape``/``shard_occupancy()``.
    """

    def __init__(self, cfg: LlamaConfig, params: Any, *,
                 mesh: Optional[Mesh] = None, tp: int = 2, **kwargs):
        if not isinstance(cfg, LlamaConfig):
            raise NoPartitionRules(
                f"the sharded engine (ShardedPagedInferenceEngine) "
                f"partitions models/llama.py's pool and projections over "
                f"tp (SERVE_RULES, pool_leaf_sharding) and has no rule for "
                f"{type(cfg).__name__}'s modules")
        if mesh is None:
            mesh = serve_mesh_for(tp)
        tp = int(mesh.shape["tp"])
        if tp < 2:
            raise ValueError(
                f"a gang needs tp >= 2 (got {tp}); use PagedInferenceEngine "
                f"for single-device serving")
        # exact-TP divisibility: head and d_ff shards must be whole —
        # padding would change reduction extents and break bit-identity
        for name, dim in (("n_heads", cfg.n_heads),
                          ("n_kv_heads", cfg.n_kv_heads),
                          ("d_ff", cfg.d_ff)):
            if dim % tp:
                raise ValueError(
                    f"{name}={dim} not divisible by tp={tp}; exact "
                    f"tensor-sharding needs whole per-shard head/ff slices")
        if kwargs.get("kernel") == "pallas":
            raise ValueError(
                "kernel='pallas' cannot serve sharded: the fused kernel is "
                "a custom call GSPMD cannot partition; use kernel='lax'")
        if kwargs.get("kernel", "auto") == "auto":
            # for the same reason a gang's "auto" is the lax read
            kwargs["kernel"] = "lax"
        self._mesh = mesh
        self._tp = tp
        self.gang_size = tp
        # the manifest compatibility key for cross-replica KV import
        # (channels/kv_transfer.py): logical mesh shape of the pool
        self.kv_mesh_shape: Tuple[int, ...] = (1, tp)
        self._repl = NamedSharding(mesh, P())
        # gang liveness: a dead shard poisons step() permanently; the
        # engine-loop death handler then fails outstanding work with the
        # gateway's failover-recognized error
        self._dead_shards: set = set()
        self._gang_fatal: Optional[str] = None
        self._gang_lock = threading.Lock()
        super().__init__(cfg, params, **kwargs)
        # rng joins the committed-replicated round inputs. PRNGKey() left
        # it uncommitted/single-device, so the first sampled round lowered
        # a SECOND decode program (rng arg UnspecifiedValue instead of the
        # warmed P() placement) whose different fusion boundaries round
        # f32 intermediates differently — a bimodal sampled stream, with
        # which program serves a round decided by dispatch timing. One
        # placement, one program, one stream. (Downstream rng values stay
        # committed: sample_token and the jitted steps only ever combine
        # it with mesh-committed operands.)
        self._rng = jax.device_put(self._rng, self._repl)
        _m.GANG_SIZE.set(float(tp), mesh=self.mesh_label)

    @property
    def mesh_label(self) -> str:
        return "x".join(str(d) for d in self.kv_mesh_shape)

    # -- construction --------------------------------------------------------

    def _build_decode_path(self, base: LlamaConfig) -> None:
        """The base build with three changes: rule overrides thread into
        the model, params and pool leaves are device_put onto the mesh
        (committed shardings make jit infer in_shardings), and every
        ``apply`` passes ``mesh`` so the activation anchors engage."""
        mesh = self._mesh
        # Donating the pool payload through a collective-bearing program
        # corrupts it on the CPU host platform: once the process heap has
        # any history, the donated executable's all-gather path
        # intermittently reads recycled buffers (wrong from the first
        # token, varying run to run; a fresh process masks it with clean
        # pages). Donation only buys back HBM, so it stays TPU/GPU-only.
        donate = {"donate_argnums": (0,)} \
            if mesh.devices.flat[0].platform != "cpu" else {}
        slots, pages = self.slots, self._pages_per_seq
        self._model = self._prefill_model = base.paged_model(
            page_size=self._page, kv_pages=self._kv_blocks,
            kernel=self._paged_kernel, kv_quant=self._kv_quant,
            rules=SERVE_RULES)
        dummy_pt = jnp.zeros((slots, pages), jnp.int32)
        # init meshless (anchors no-op without a mesh), THEN place: the
        # pool shards on kv_heads, index leaves and params replicate
        # except the head/ff-sharded projection kernels
        cache = init_cache(lambda: self._model.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
            page_table=dummy_pt))
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.device_put(
                leaf, pool_leaf_sharding(mesh, path, leaf)),
            cache)
        self._adopt_cache(cache)
        self.params = shard_params(self.params, mesh)
        self._payload_shardings = [leaf.sharding for leaf in self._payload]

        def prefill_step(pool, state, job, params, key, width):
            # the base program with the mesh on every apply; the index
            # leaves are built inside it
            return self._prefill_program(
                pool, state, job, params, key, width, mesh=mesh)

        self._prefill_step = jax.jit(
            prefill_step, static_argnames=("width",),
            **({"donate_argnums": (0, 1, 2)} if donate else {}))
        # both halves leave replicated, as the decode program was warmed
        # to take the rng
        self._split_rng = jax.jit(
            lambda rng: tuple(jax.random.split(rng)),
            out_shardings=(self._repl, self._repl))

        def decode_step(payload, params, cur, pos, page_table,
                        greedy_mask, rng):
            cache = self._assemble_cache(payload, pos)
            logits, updated = self._model.apply(
                {"params": params, "cache": cache}, cur[:, None], mesh=mesh,
                page_table=page_table, mutable=["cache"])
            nxt, rng = self._pick_next(logits[:, -1], greedy_mask, rng)
            payload, new_pos = self._split_cache(updated["cache"])
            return payload, new_pos, nxt, rng

        self._decode_step = jax.jit(decode_step, **donate)

        def verify_step(payload, params, cur, prop, prop_len, pos,
                        page_table, greedy_mask, rng):
            cache = self._assemble_cache(payload, pos)
            toks = jnp.concatenate([cur[:, None], prop], axis=1)
            logits, updated = self._model.apply(
                {"params": params, "cache": cache}, toks, mesh=mesh,
                page_table=page_table, mutable=["cache"])
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt, rng = self._pick_next(logits[:, 0], greedy_mask, rng)
            payload, _ = self._split_cache(updated["cache"])
            packed, new_cur, new_pos = self._accept(prop, prop_len,
                                                    greedy, nxt, pos)
            return payload, packed, new_cur, new_pos, rng

        self._verify_step = jax.jit(verify_step, **donate)

    def _warm_compile(self, step, payload, mids, mask, rng):
        """AOT warm with the REAL shardings: abstract avals carry the
        pool placement and replicated round inputs, so the warmed
        executable is the one the first request dispatches."""
        repl = self._repl
        payload = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
                   for s, sh in zip(payload, self._payload_shardings)]
        mids = tuple(jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=repl)
                     for m in mids)
        pt = jax.ShapeDtypeStruct((self.slots, self._pages_per_seq),
                                  jnp.int32, sharding=repl)
        mask = jax.ShapeDtypeStruct(mask.shape, mask.dtype, sharding=repl)
        rng = jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=repl)
        step.lower(payload, self.params, *mids, pt, mask, rng).compile()

    # -- round inputs: committed-replicated, upload-once ----------------------

    def _device_inputs(self):
        """Base discipline (upload once, previous round's outputs in the
        steady state) with the uploads COMMITTED replicated on the mesh —
        an uncommitted single-device array among committed operands
        would make jit's device-set resolution placement-dependent."""
        if self._cur_dev is None:
            self._cur_dev = jax.device_put(np.array(self._cur), self._repl)
        if self._pos_dev is None:
            self._pos_dev = jax.device_put(
                np.array(self._pos, np.int32), self._repl)
        if self._mask_dev is None:
            self._mask_dev = jax.device_put(
                np.array(self._greedy_mask()), self._repl)
        return self._cur_dev, self._pos_dev, self._mask_dev

    def _page_table_dev(self):
        if self._pt_dev is None:
            self._pt_dev = jax.device_put(
                np.array(self._tables), self._repl)
        return self._pt_dev

    def _upload(self, array):
        """A prefill job's buffer, committed replicated like every other
        round input: a host array would reach the job's first program
        unplaced, and that is another program than the one its later
        rounds compile."""
        PREFILL_CALLS.inc()
        return jax.device_put(array, self._repl)

    # -- gang liveness -------------------------------------------------------

    @property
    def gang_intact(self) -> bool:
        """False once any shard host has been marked dead. Recovery reads
        this: a gang that lost a host is never re-adopted (all-or-nothing)."""
        return not self._dead_shards

    def mark_host_dead(self, shard: int, reason: str = "host dead") -> None:
        """Declare one shard host of the gang dead. Engine-fatal by
        design: the next ``step()`` raises ``GangHostDead``, the loop
        death handler fails all outstanding requests with ``"engine loop
        died"`` (the gateway failover prefix — fenced tokens are kept and
        the stream resumes on a sibling), and health retires the replica.
        Idempotent per shard; a parked loop is woken so death is prompt."""
        with self._gang_lock:
            if shard in self._dead_shards:
                return
            self._dead_shards.add(shard)
            if self._gang_fatal is None:
                self._gang_fatal = (
                    f"gang shard {shard}/{self._tp} dead: {reason}")
        self.queue.work_available.set()

    def step(self) -> bool:
        if self._gang_fatal is not None:
            raise GangHostDead(self._gang_fatal)
        return super().step()

    # -- observability -------------------------------------------------------

    def shard_occupancy(self) -> List[int]:
        """Allocated KV blocks per shard. The shared logical block table
        makes every shard hold the same block set, so the list is uniform
        — the skew gauge this feeds is a tripwire for per-shard
        allocators diverging, not a load-balancing signal."""
        ks = self.kv.stats()
        allocated = ks.blocks_total - ks.blocks_free
        return [allocated] * self._tp

    def stats(self):
        s = super().stats()
        occ = self.shard_occupancy()
        for i, blocks in enumerate(occ):
            _m.SHARD_KV_BLOCKS.set(float(blocks), mesh=self.mesh_label,
                                   shard=str(i))
        _m.SHARD_SKEW.set(float(max(occ) - min(occ)), mesh=self.mesh_label)
        return s
