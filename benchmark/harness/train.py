"""The training cell: ``parallel.make_train_step`` on ``models/llama.py``
over a mesh of all the cell's chips, inside an ``@op`` of a local workflow,
fed by a host pipeline that packs documents while the window runs."""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmark.harness import accounting, reference, traffic as gen
from benchmark.harness import trace as xtrace
from benchmark.harness.serve import init_params, llama_config

#: the program's first loss against the plain float32 reference's, relative.
#: The loss at random weights is about ln(vocab) = 10.4; bf16 activations
#: (8 bits) move single logits by about 1e-2, and the mean over 32,768
#: positions averages that down. 5e-3 relative (0.05 nats) is an order above
#: what bf16 rounding leaves and far below a wrong mask or position rule,
#: which move the loss by tenths.
LOSS_RTOL = 5e-3


def _state_shardings(mesh, boxed, state_shape):
    """The layout ``make_train_step`` gives a ``TrainState``: parameters by
    their logical axes, optimizer moments like the parameters they mirror,
    everything else replicated. Restated here so that the state can be
    *made* sharded: built on one chip first, 2 B float32 parameters with two
    Adam moments do not fit it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lzy_tpu.models.common import param_logical_axes
    from lzy_tpu.parallel.sharding import tree_shardings

    param_sh = tree_shardings(mesh, param_logical_axes(boxed), None)
    replicated = NamedSharding(mesh, P())
    structure = jax.tree_util.tree_structure(state_shape.params)

    def mirror(node):
        return jax.tree_util.tree_structure(node) == structure

    opt_sh = jax.tree_util.tree_map(
        lambda node: param_sh if mirror(node)
        else jax.tree_util.tree_map(lambda _: replicated, node),
        state_shape.opt_state, is_leaf=mirror)
    return type(state_shape)(step=replicated, params=param_sh,
                             opt_state=opt_sh)


class Feeder(threading.Thread):
    """The input pipeline: packs batches on the host and keeps a few ahead."""

    def __init__(self, batches, depth: int):
        super().__init__(daemon=True, name="bench-feeder")
        self.batches, self.out = batches, queue.Queue(maxsize=depth)
        self._halt = threading.Event()

    def run(self):
        for batch in self.batches:
            while not self._halt.is_set():
                try:
                    self.out.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._halt.is_set():
                return

    def stop(self):
        self._halt.set()
        self.join()


def _job(config, tr, args, meter, phases, trace_dir, chips) -> dict:
    """The body of the ``@op``: everything that touches the devices."""
    import jax
    import optax

    from lzy_tpu.models import llama, unbox
    from lzy_tpu.models.common import param_logical_axes
    from lzy_tpu.parallel import TrainState, make_train_step, mesh_for

    cfg = llama_config(config)
    layout = config["mesh"]
    mesh = mesh_for(chips, **layout)
    boxed = jax.eval_shape(lambda k: llama.init_params(cfg, k)[0],
                           jax.random.PRNGKey(0))
    tx = optax.adamw(float(tr["learning_rate"]))
    step, _, batch_sharding = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=param_logical_axes(boxed),
        batch_logical_axes=("batch", "seq"))
    state_shape = jax.eval_shape(
        lambda k: TrainState.create(unbox(llama.init_params(cfg, k)[0]), tx),
        jax.random.PRNGKey(0))
    shardings = _state_shardings(mesh, boxed, state_shape)
    params = init_params(cfg, args.seed, out_shardings=shardings.params)
    state = jax.jit(lambda p: TrainState.create(p, tx),
                    out_shardings=shardings)(params)
    del params
    jax.block_until_ready(state)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    phases.mark("weights")

    feeder = Feeder(gen.packed_batches(tr, seed=args.seed,
                                       vocab=cfg.vocab_size),
                    tr.get("prefetch", 4))
    feeder.start()

    def put(batch):
        return {k: jax.device_put(v, batch_sharding)
                for k, v in batch.items()}

    try:
        first = put(feeder.out.get())
        ref_loss = reference.loss(
            state.params, first["tokens"], first["segments"],
            n_layers=cfg.n_layers, theta=cfg.rope_theta, eps=cfg.norm_eps)
        phases.mark("correctness")
        losses, ends = [], []
        state, metrics = step(state, first)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        for _ in range(tr["warm_steps"]):
            state, metrics = step(state, put(feeder.out.get()))
            losses.append(float(jax.block_until_ready(metrics["loss"])))
        phases.mark("warm_up")

        compiles_before = meter.compiles
        trace_span = None
        t_open = time.monotonic()
        t_close = t_open + args.seconds
        trace_at = t_open + min(tr.get("trace_after_s", 5.0),
                                args.seconds / 4)
        trace_for = min(tr.get("trace_s", 4.0), args.seconds / 2)
        ends.append(t_open)
        window_losses, waits = [], []
        tracing = False
        # a step is over when its loss is on the host; the next batch is put
        # on the devices while the step before still runs
        nxt = put(feeder.out.get())
        while True:
            now = time.monotonic()
            if trace_dir and not tracing and trace_span is None \
                    and now >= trace_at:
                xtrace.start(trace_dir)
                tracing, t_trace = True, time.monotonic()
            if now >= t_close:
                break
            state, metrics = step(state, nxt)
            t0 = time.monotonic()
            nxt = put(feeder.out.get())
            waits.append(time.monotonic() - t0)
            window_losses.append(float(jax.block_until_ready(
                metrics["loss"])))
            ends.append(time.monotonic())
            if tracing and ends[-1] - t_trace >= trace_for:
                xtrace.stop()
                tracing, trace_span = False, (t_trace, time.monotonic())
        if tracing:
            xtrace.stop()
            trace_span = (t_trace, time.monotonic())
        compiles = meter.compiles - compiles_before
    finally:
        feeder.stop()

    # the last step may end after t_close: it is not counted, and the run
    # overshoots by less than one step
    rate = accounting.step_rate(ends, tr["batch"] * tr["seq"], t_open,
                                t_close)
    finite = all(np.isfinite(x) for x in losses + window_losses)
    k = max(1, len(window_losses) // 5)
    falls = bool(window_losses) and (
        np.mean(window_losses[-k:]) < np.mean(window_losses[:k]))
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    return {
        "values": {"rate": rate["tokens_per_s"]},
        "attempted": len(window_losses), "failed": 0,
        "correct": bool(finite and falls and rel <= LOSS_RTOL
                        and compiles == 0
                        and rate["tokens_per_s"] is not None),
        "notes": {"steps": rate["steps"], "first_loss": losses[0],
                  "reference_loss": ref_loss, "loss_rel_diff": rel,
                  "loss_tolerance": LOSS_RTOL,
                  "window_loss_first_last": [window_losses[0],
                                             window_losses[-1]]
                  if window_losses else None,
                  "compiles_in_window": compiles,
                  "overshoot_s": time.monotonic() - t_close},
        "obs": {"t_open": t_open, "t_close": t_close, "step_ends": ends,
                "input_waits": waits, "trace_span": trace_span,
                "compiles_in_window": compiles,
                "rate": rate,
                "model": {"n_params": n_params, "cfg": {
                    "n_layers": cfg.n_layers, "d_model": cfg.d_model,
                    "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                    "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
                    "vocab_size": cfg.vocab_size},
                    "batch": tr["batch"], "seq": tr["seq"],
                    "chips": chips}},
    }


def run(files: dict, args, meter, phases, trace_dir) -> dict:
    from lzy_tpu import Lzy, op
    from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

    config, tr, chips = files["config"], files["traffic"], \
        files["cell"]["chips"]
    holder = {}

    @op
    def train(seed: int) -> dict:
        # the op's result goes through the workflow's storage; what the
        # readers need stays in this process
        holder["out"] = _job(config, tr, args, meter, phases, trace_dir,
                             chips)
        return {"steps": holder["out"]["attempted"]}

    registry = DefaultStorageRegistry()
    registry.register_storage(
        "default", StorageConfig(uri=f"mem://bench-train-{args.seed}"),
        default=True)
    with Lzy(storage_registry=registry).workflow("bench-train"):
        dict(train(args.seed))
    return holder["out"]
