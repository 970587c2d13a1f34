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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving.engine import PagedInferenceEngine
from lzy_tpu.serving.prefill import ProgramBuild
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

    def _program_build(self) -> ProgramBuild:
        """What the mesh changes about the engine's programs: rule
        overrides thread into the model, every ``apply`` passes ``mesh`` so
        the activation anchors engage, and everything that is not sharded
        is COMMITTED replicated: the rng's halves as they leave their split
        (the decode program was warmed to take the rng so), the round
        inputs (the base discipline: upload once, the previous round's
        outputs in the steady state; an uncommitted single-device array
        among committed operands would make jit's device-set resolution
        placement-dependent), a prefill job's buffer, and ``warmup()``'s
        avals beside the pool's own placement.

        Donating the pool payload through a collective-bearing program
        corrupts it on the CPU host platform: once the process heap has
        any history, the donated executable's all-gather path
        intermittently reads recycled buffers (wrong from the first
        token, varying run to run; a fresh process masks it with clean
        pages). Donation only buys back HBM, so it stays TPU/GPU-only."""
        return ProgramBuild(
            model_kw={"rules": SERVE_RULES}, apply_kw={"mesh": self._mesh},
            donate=self._mesh.devices.flat[0].platform != "cpu",
            replicated=self._repl)

    def _adopt_cache(self, tree) -> None:
        """The cache was initialised meshless (anchors no-op without a
        mesh); it is placed here: the pool shards on kv_heads, index leaves
        and params replicate except the head/ff-sharded projection kernels
        (committed shardings make jit infer in_shardings)."""
        mesh = self._mesh
        super()._adopt_cache(jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.device_put(
                leaf, pool_leaf_sharding(mesh, path, leaf)),
            tree))
        self.params = shard_params(self.params, mesh)

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
