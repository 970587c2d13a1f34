"""The serving cells: one ``PagedInferenceEngine`` replica behind
``GatewayService``, in process, driven open loop (``open_loop``) or by
closed-loop workflow clients (``closed_loop_units``).

From the program this takes the system under test (engine, fleet, gateway,
``llm``, workflows) and its counters. Everything that measures is here: the
clock, the spans around the layers' boundaries, the sampler, the schedule."""

from __future__ import annotations

import os
import queue
import re
import threading
import time

import numpy as np

from benchmark.harness import accounting, reference, traffic as gen
from benchmark.harness import trace as xtrace
from lzy_tpu.channels.token_stream import TokenStreamChannel

#: how far below the reference's best logit a served token may sit. Logits
#: of this model at random weights spread by about 1.3 (0.02 * sqrt(4096));
#: bf16 keeps 8 bits, and two correct bf16 programs that round activations at
#: different points differ by about 1e-2 on a logit after 8 layers
#: (``chip_smoke.py``'s argument), about 1.5e-2 after 16 (errors add in
#: quadrature over layers). A token is accepted within 0.15 of the float32
#: reference's best: ten times that noise, a ninth of the spread. A wrong
#: token sits several units short; float16-precision activations or a
#: dropped layer would fail.
LOGIT_TIE_TOL = 0.15


def llama_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``LlamaConfig``; ``program`` holds the program's own switches."""
    import jax.numpy as jnp

    from lzy_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_layers=doc["num_hidden_layers"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"],
        d_ff=doc["intermediate_size"], rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"],
        tie_embeddings=bool(doc["tie_word_embeddings"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)
    if cfg.head_dim != doc["head_dim"]:
        raise ValueError(f"head_dim {cfg.head_dim} != {doc['head_dim']}")
    if doc.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    return cfg


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served or trained in."""
    import jax

    from lzy_tpu.models import llama, unbox

    make = jax.jit(lambda key: unbox(llama.init_params(cfg, key)[0]),
                   out_shardings=out_shardings)
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (2 ** 31))))


class TimedStream(TokenStreamChannel):
    """The client's view of a stream, with the arrival time of every
    publish: one tuple append per publish, on the thread that publishes."""

    def __init__(self):
        super().__init__()
        self.stamps = []          # (arrival time, tokens received so far)

    def publish(self, position, tokens):
        super().publish(position, tokens)
        self.stamps.append((time.monotonic(), position + len(tokens)))


class Spans:
    """The benchmark's spans at two boundaries: around ``gateway.generate``
    (this object stands in for the gateway, for the load generator and for
    ``llm.configure`` alike) and around the engine's submit -> finish (the
    engine factory wraps ``submit``). It adds the liveness probe that lets
    the run cancel what is outstanding when the window ends."""

    def __init__(self, gateway):
        self.gateway = gateway
        self.model_name = gateway.model_name
        self.model_digest = gateway.model_digest
        self.alive = True
        self.calls = []            # (t_in, t_out, engine_s, key)
        #: when on, a call that brings no stream of its own is given a
        #: TimedStream, so that output tokens can be counted as they arrive
        self.stamp_tokens = False
        self.rows = []             # (prompt length, its TimedStream)
        self._tls = threading.local()

    def _is_alive(self) -> bool:
        return self.alive

    def wrap_engine(self, engine):
        submit, tls = engine.submit, self._tls

        def timed_submit(prompt, **kw):
            req = submit(prompt, **kw)
            held = getattr(tls, "reqs", None)
            if held is not None:
                held.append(req)
            return req

        engine.submit = timed_submit
        return engine

    def generate(self, prompt, **kw):
        self._tls.reqs = reqs = []
        kw.setdefault("liveness", self._is_alive)
        if self.stamp_tokens and kw.get("stream") is None:
            kw["stream"] = TimedStream()
        if isinstance(kw.get("stream"), TimedStream):
            self.rows.append((len(prompt), kw["stream"]))
        t_in = time.monotonic()
        try:
            return self.gateway.generate(prompt, **kw)
        finally:
            t_out = time.monotonic()
            engine_s = sum((r.finished_at or t_out) - r.submitted_at
                           for r in reqs)
            self.calls.append((t_in, t_out, engine_s, tuple(prompt[:8])))
            self._tls.reqs = None


def build(config: dict, seed: int, phases):
    """Weights, engine (warm), fleet of one, gateway. Returns a dict."""
    import jax

    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)
    from lzy_tpu.llm.backend import model_digest_for
    from lzy_tpu.serving import PagedInferenceEngine

    cfg = llama_config(config)
    params = init_params(cfg, seed)
    phases.mark("weights")
    eng_kw = dict(config["engine"])
    page = eng_kw["page_size"]
    holder = {}

    def factory():
        engine = PagedInferenceEngine(cfg, params, **eng_kw)
        engine.warmup()
        if "spans" in holder:
            holder["spans"].wrap_engine(engine)
        holder["engine"] = engine
        return engine

    fleet = ReplicaFleet(factory)
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(page),
                             model_name=config["name"], page_size=page,
                             **config.get("gateway", {}))
    gateway.model_digest = model_digest_for(config["name"], cfg, seed=seed)
    spans = holder["spans"] = Spans(gateway)
    try:
        fleet.add_replica()
    except BaseException:
        gateway.close()
        raise
    phases.mark("engine")
    leaves = jax.tree_util.tree_leaves(params)
    return {"cfg": cfg, "params": params, "gateway": gateway,
            "engine": holder["engine"], "spans": spans, "fleet": fleet,
            "param_bytes": sum(x.nbytes for x in leaves),
            "kv_bytes_per_token": 2 * cfg.n_layers * cfg.n_kv_heads
            * cfg.head_dim * np.dtype(cfg.dtype).itemsize}


def distinct_prompt_lengths(tr: dict) -> list:
    return sorted(set(gen.quantiles(tr["prompt_len"],
                                    tr["prompt_len"]["levels"])))


def warm_and_check(sys: dict, tr: dict, seed: int, phases) -> dict:
    """One request of every prompt length the cell will send, so that every
    program the window needs exists before it opens; then the correctness
    requests against the plain reference."""
    import jax.numpy as jnp

    spans, cfg = sys["spans"], sys["cfg"]
    rng = np.random.default_rng([seed, 9])
    lengths = distinct_prompt_lengths(tr)
    replies = {}

    def one(i, n):
        prompt = gen.tokens_for(np.random.default_rng([seed, 9, i]), n,
                                cfg.vocab_size)
        replies[i] = spans.generate(prompt, max_new_tokens=2, greedy=True,
                                    timeout_s=600.0)

    # the first alone: it pays what a fresh engine's first request pays
    one(0, lengths[0])
    threads = [threading.Thread(target=one, args=(i, n))
               for i, n in enumerate(lengths) if i]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bad = [r for r in replies.values() if len(r.get("tokens", [])) != 2]
    if bad or len(replies) != len(lengths):
        raise AssertionError(f"warm-up requests failed: {bad[:2]}")
    phases.mark("warm_up")

    chk = tr["correctness"]
    fits = [n for n in lengths if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    worst, verdicts = 0.0, []
    for n in picks:
        prompt = gen.tokens_for(rng, n, cfg.vocab_size)
        stream = TimedStream()
        reply = spans.generate(prompt, max_new_tokens=chk["decode_tokens"],
                               greedy=True, stream=stream, timeout_s=600.0)
        tokens = list(reply["tokens"])
        if len(tokens) != chk["decode_tokens"] or stream.tokens() != tokens:
            raise AssertionError(
                f"correctness request of {n} tokens returned {len(tokens)} "
                f"tokens, {len(stream.tokens())} streamed")
        full = prompt + tokens
        padded = jnp.asarray([full + [0] * (chk["pad_to"] - len(full))],
                             jnp.int32)
        rows = jnp.arange(n - 1, len(full) - 1)
        logits = np.asarray(reference.logits_at(
            sys["params"], padded, rows, n_layers=cfg.n_layers,
            theta=cfg.rope_theta, eps=cfg.norm_eps))
        gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
        worst = max(worst, float(gap.max()))
        verdicts.append({"prompt": n, "max_logit_gap": float(gap.max()),
                         "identical": int((gap == 0).sum())})
    phases.mark("correctness")
    return {"ok": worst <= LOGIT_TIE_TOL, "worst_logit_gap": worst,
            "tolerance": LOGIT_TIE_TOL, "requests": verdicts}


def sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


class Sampler(threading.Thread):
    """Engine occupancy and pool use on a fixed period, off the engine's
    thread: ``stats()`` reads counters, it does not take the round's lock."""

    def __init__(self, engine, period_s: float):
        super().__init__(daemon=True, name="bench-sampler")
        self.engine, self.period = engine, period_s
        #: (t, busy, slots, blocks_total, free, queue_depth, cached)
        self.rows = []
        self._halt = threading.Event()

    def run(self):
        nxt = time.monotonic()
        while not self._halt.is_set():
            s = self.engine.stats()
            self.rows.append((time.monotonic(), s.busy, s.slots,
                              s.kv_blocks_total, s.kv_blocks_free,
                              s.queue_depth, s.kv_blocks_cached))
            nxt += self.period
            self._halt.wait(max(0.0, nxt - time.monotonic()))

    def stop(self):
        self._halt.set()
        self.join()


_PHASE_SUM = re.compile(
    r'lzy_engine_round_phase_seconds_sum\{phase="(\w+)"\} (\S+)')


def counters(sys: dict) -> dict:
    """The program's counters the readers use, as they stand now."""
    from lzy_tpu.utils.metrics import REGISTRY

    agg = sys["fleet"].aggregate()
    out = {k: agg.get(k, 0) for k in (
        "tokens_generated", "requests_finished", "decode_steps",
        "decode_rows", "decode_tokens", "prefix_hit_tokens",
        "prefix_lookup_tokens")}
    for phase, value in _PHASE_SUM.findall(REGISTRY.exposition()):
        out["round_phase." + phase] = float(value)
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


class Window:
    """Opens and closes the measured window: counters at both ends, the
    sampler, the compile count, and with ``--trace 1`` a profiler trace of
    ``trace_s`` seconds that starts ``trace_after_s`` into the window."""

    def __init__(self, sys, tr, meter, seconds, trace_dir):
        self.sys, self.tr, self.meter = sys, tr, meter
        self.seconds, self.trace_dir = seconds, trace_dir
        self.trace_span = None

    def open(self):
        self.sampler = Sampler(self.sys["engine"],
                               self.tr.get("sample_period_s", 0.1))
        self.before = counters(self.sys)
        self.cpu_before = sum(os.times()[:2])
        self.compiles_before = self.meter.compiles
        self.t_open = time.monotonic()
        self.t_close = self.t_open + self.seconds
        self.sampler.start()
        if self.trace_dir:
            self._tracer = threading.Thread(target=self._trace, daemon=True)
            self._tracer.start()
        return self

    def _trace(self):
        span = min(self.tr.get("trace_s", 4.0), self.seconds / 2)
        time.sleep(min(self.tr.get("trace_after_s", 5.0), self.seconds / 4))
        t0 = time.monotonic()
        xtrace.start(self.trace_dir)
        time.sleep(span)
        t1 = time.monotonic()
        xtrace.stop()
        self.trace_span = (t0, t1)

    def close(self):
        """At ``t_close``: the counters' and compile count's reading."""
        self.after = counters(self.sys)
        self.process_cpu_s = sum(os.times()[:2]) - self.cpu_before
        self.compiles = self.meter.compiles - self.compiles_before
        self.sampler.stop()

    def finish(self):
        if self.trace_dir:
            self._tracer.join()

    def observed(self) -> dict:
        rows = [r for r in self.sampler.rows
                if self.t_open <= r[0] <= self.t_close]
        return {"counters": delta(self.after, self.before), "samples": rows,
                "t_open": self.t_open, "t_close": self.t_close,
                "compiles_in_window": self.compiles,
                "process_cpu_s": self.process_cpu_s,
                "trace_span": self.trace_span}


def run_open_loop(sys, tr, *, seed, seconds, meter, trace_dir, phases):
    """Requests are sent when due, whatever the system's state. One thread
    keeps the schedule and hands each request to a pool of blocked callers."""
    spans, vocab = sys["spans"], sys["cfg"].vocab_size
    ramp_s = float(tr["ramp_s"])
    ramp = gen.open_loop_segment(tr, seed=seed, stream=1, duration_s=ramp_s,
                                 vocab=vocab)
    due_in = gen.open_loop_segment(tr, seed=seed, stream=2,
                                   duration_s=seconds, vocab=vocab)
    todo = queue.Queue()

    def caller():
        while True:
            r = todo.get()
            if r is None:
                return
            r["stream"] = stream = TimedStream()
            r["sent"] = time.monotonic()
            try:
                reply = spans.generate(
                    r["prompt"], max_new_tokens=r["max_new_tokens"],
                    greedy=True, stream=stream, timeout_s=600.0)
                r["status"] = reply.get("status", "ok")
                r["returned"] = len(reply.get("tokens", []))
                r["done"] = time.monotonic()
            except Exception as e:  # noqa: BLE001 — a refusal is a failure
                r["status"] = f"{type(e).__name__}: {e}"

    callers = [threading.Thread(target=caller, daemon=True)
               for _ in range(tr["caller_threads"])]
    for t in callers:
        t.start()

    def send(batch, t0):
        for r in batch:
            r["due"] += t0
            sleep_until(r["due"])
            todo.put(r)

    t_start = time.monotonic()
    send(ramp, t_start)
    sleep_until(t_start + ramp_s)
    phases.mark("ramp")
    win = Window(sys, tr, meter, seconds, trace_dir).open()
    send(due_in, win.t_open)
    sleep_until(win.t_close)
    win.close()
    t_cut = win.t_close + float(tr["first_token_wait_s"])
    sleep_until(t_cut)
    for r in due_in:
        r["stamps"] = list(r["stream"].stamps) if "stream" in r else []
    spans.alive = False          # cancel what is outstanding
    for _ in callers:
        todo.put(None)
    for t in callers:
        t.join(timeout=30.0)
    t_done = time.monotonic()
    win.finish()
    lat = accounting.open_loop_latencies(
        due_in, t_cut, ttft_q=tr["ttft_percentile"],
        tpot_q=tr["tpot_percentile"],
        tpot_min_tokens=tr["tpot_min_tokens"])
    short = [r for r in due_in if r.get("status") == "ok"
             and r.get("returned") != r["max_new_tokens"]]
    refused = [r for r in due_in if r.get("status") not in
               (None, "ok", "cancelled")]
    obs = win.observed()
    rows = stamped_rows(spans)
    lat["silence"] = accounting.longest_silence(rows, win.t_open,
                                                win.t_close)
    obs.update({"latencies": lat, "requests": due_in,
                "calls": list(spans.calls), "rows": rows})

    def backlog(t):
        """Requests sent and not finished at ``t``, ramp's included."""
        return sum(1 for r in ramp + due_in if r.get("sent", 1e18) <= t
                   and r.get("done", 1e18) > t)

    mid = (win.t_open + win.t_close) / 2
    return {
        "values": lat,
        "attempted": len(due_in),
        "failed": lat["missing"] + len(short),
        "correct": not short and not refused,
        "notes": {"latencies": lat,
                  "refused": [r["status"] for r in refused][:3],
                  "backlog_open_mid_close": [backlog(win.t_open),
                                             backlog(mid),
                                             backlog(win.t_close)],
                  "overshoot_s": t_done - win.t_close},
        "obs": obs,
    }


def _fan_in(generations) -> list:
    """The op after a batch: what a map-over-a-dataset workflow does with
    its answers. Returns each row's token count and status."""
    return [[len(g.tokens), g.status] for g in generations]


def run_closed_loop_units(sys, tr, *, seed, seconds, meter, trace_dir,
                          phases):
    """``clients`` workflows, each repeating: ``llm.generate_batch`` of one
    unit's rows, a fan-in op, the next unit. All of them keep issuing until
    the window ends; what is outstanding then is cancelled."""
    from lzy_tpu import Lzy, llm, op
    from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

    spans, vocab = sys["spans"], sys["cfg"].vocab_size
    deck = gen.unit_deck(tr, seed=seed)
    n_clients = tr["clients"]
    fan_in = op(_fan_in)
    row_client = {}        # first tokens of a prompt -> its client
    clients = [{"units": 0, "error": None} for _ in range(n_clients)]
    go = threading.Event()

    def client(c: int):
        registry = DefaultStorageRegistry()
        registry.register_storage(
            "default", StorageConfig(uri=f"mem://bench-{seed}-{c}"),
            default=True)
        lzy = Lzy(storage_registry=registry)
        me = clients[c]
        go.wait()
        try:
            with lzy.workflow(f"bench-backlog-{c}"):
                k = 0
                while spans.alive:
                    unit = deck[(c + k * n_clients) % len(deck)]
                    budget = unit["max_new_tokens"]
                    if k == 0:
                        # the first unit is cut to (c + 1) / clients of its
                        # length: the clients start together, and this
                        # spreads their later units over a unit's time, so
                        # that their waves do not align
                        budget = max(1, budget * (c + 1) // n_clients)
                    prompts = gen.unit_prompts(unit, seed=seed, client=c,
                                               k=k, vocab=vocab)
                    for prompt in prompts:
                        row_client[tuple(prompt[:8])] = c
                    done = list(fan_in(llm.generate_batch(
                        prompts, max_new_tokens=budget, greedy=True,
                        cache=False, timeout_s=600.0)))
                    if any(s != "ok" for _, s in done):
                        break
                    got = sum(n for n, _ in done)
                    if got != budget * len(prompts):
                        me["error"] = (f"unit {k}: {got} tokens, asked "
                                       f"{budget} a row")
                        break
                    me["units"] += 1
                    k += 1
        except Exception as e:  # noqa: BLE001 — reported, never hidden
            if spans.alive:
                me["error"] = f"{type(e).__name__}: {e}"

    spans.stamp_tokens = True
    llm.configure(spans)
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    go.set()
    time.sleep(float(tr["ramp_s"]))
    phases.mark("ramp")
    win = Window(sys, tr, meter, seconds, trace_dir).open()
    sleep_until(win.t_close)
    win.close()
    spans.alive = False
    for t in threads:
        t.join(timeout=60.0)
    t_done = time.monotonic()
    llm.configure(None)
    win.finish()
    rows = stamped_rows(spans)
    arrived = accounting.arrived_tokens(
        [stamps for _, stamps in rows], win.t_open, win.t_close)
    errors = [c["error"] for c in clients if c["error"]]
    units = sum(c["units"] for c in clients)
    obs = win.observed()
    obs.update({"clients": clients, "calls": list(spans.calls),
                "row_client": row_client, "rows": rows})
    return {
        "values": {"rate": arrived / seconds},
        "attempted": units * tr["unit_rows"],
        "failed": len(errors),
        "correct": not errors and arrived > 0,
        "notes": {"units": units, "overshoot_s": t_done - win.t_close,
                  "longest_silence_s": accounting.longest_silence(
                      rows, win.t_open, win.t_close),
                  "errors": errors[:3]},
        "obs": obs,
    }


def stamped_rows(spans) -> list:
    """Every streamed request so far: (prompt length, [(arrival time,
    tokens so far)])."""
    return [(n, list(stream.stamps)) for n, stream in spans.rows]


RUNNERS = {"open_loop": run_open_loop,
           "closed_loop_units": run_closed_loop_units}


def run(files: dict, args, meter, phases, trace_dir) -> dict:
    config, tr = files["config"], files["traffic"]
    sys = build(config, args.seed, phases)
    try:
        check = warm_and_check(sys, tr, args.seed, phases)
        out = RUNNERS[tr["kind"]](sys, tr, seed=args.seed,
                                  seconds=args.seconds, meter=meter,
                                  trace_dir=trace_dir, phases=phases)
    finally:
        sys["gateway"].close()
    obs = out["obs"]
    out["correct"] = bool(out["correct"] and check["ok"]
                          and obs["compiles_in_window"] == 0)
    out["notes"].update({"correctness": check,
                         "process_cpu_s": obs["process_cpu_s"],
                         "compiles_in_window": obs["compiles_in_window"]})
    if obs["trace_span"]:
        out["notes"]["resident_tokens_traced"] = accounting.resident_tokens(
            obs["rows"], *obs["trace_span"])
    obs["model"] = {
        "param_bytes": sys["param_bytes"],
        "kv_bytes_per_token": sys["kv_bytes_per_token"],
        "slots": config["engine"]["slots"],
    }
    return out
