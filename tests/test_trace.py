"""JAX profiler integration (SURVEY §5.1): trace capture, step annotation,
and op-level profiling whose artifacts land in workflow storage."""

import json

import jax
import jax.numpy as jnp
import pytest

from lzy_tpu import op
from lzy_tpu.service import InProcessCluster
from lzy_tpu.utils import trace
from lzy_tpu.utils.trace import annotate_step, profiled


class TestProfiled:
    def test_capture_produces_artifacts(self, tmp_path):
        with profiled(str(tmp_path / "trace")) as logdir:
            with annotate_step(0):
                float(jax.jit(lambda x: (x @ x).sum())(jnp.ones((16, 16))))
        import os

        produced = [os.path.join(r, f)
                    for r, _, fs in os.walk(logdir) for f in fs]
        assert produced, "no trace artifacts captured"

    def test_capture_carries_the_engine_loop_spans_and_a_clock_anchor(
            self, tmp_path):
        """``profiled()`` turns the span recorder on: the engine loop's
        spans land in the capture's host plane as annotations, with an
        anchor that ties the profiler's clock to ``time.monotonic()``."""
        import glob

        from jax.profiler import ProfileData

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.serving import PagedInferenceEngine

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
        eng = PagedInferenceEngine(cfg, params, slots=2)
        warm = eng.submit([1, 2], max_new_tokens=2)
        while not warm.done:
            eng.step()
        assert trace.ON is False
        with profiled(str(tmp_path / "trace")) as logdir:
            assert trace.ON is True
            eng.start()
            req = eng.submit([5, 9, 3], max_new_tokens=4)
            assert req.wait(60)
            eng.close()
        assert trace.ON is False
        # the recorder was drained into spans.jsonl beside the trace
        head, *records = [json.loads(line) for line in
                          open(f"{logdir}/{trace.SPANS_FILE}")]
        assert head["records"] == len(records) and head["dropped"] == 0
        assert {trace.ENGINE_REQUEST_QUEUED, trace.ENGINE_PREFILL_FENCE} \
            <= {r["name"] for r in records}
        rounds = [r for r in records if r["name"] == trace.ENGINE_ROUND
                  and r["thread"] == "inference-engine"]
        path, = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
        lines = [(line.name, [(float(e.start_ns), float(e.duration_ns),
                               e.name) for e in line.events])
                 for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU" for line in plane.lines]
        # the loop's thread has a name of its own, so its line is not one
        # of many called ``python3``
        host, = [events for name, events in lines
                 if name.startswith("lzy-engine-")]
        names = {n for _, _, n in host}
        assert {trace.ENGINE_ROUND, trace.ENGINE_PREFILL,
                trace.ENGINE_DECODE_FENCE} <= names
        assert not any(n.startswith(("gateway.", "engine.request"))
                       for n in names)
        # the anchors place a record's stamp on the profiler's clock: each
        # round's annotation starts where its record says
        offsets = sorted(
            s - int(n[len(trace.CLOCK_ANCHOR):])
            for _, events in lines for s, _, n in events
            if n.startswith(trace.CLOCK_ANCHOR))
        assert offsets
        offset = offsets[len(offsets) // 2]
        starts = sorted(s for s, _, n in host if n == trace.ENGINE_ROUND)
        assert len(starts) == len(rounds) > 0
        for annotated, r in zip(starts, rounds):
            assert abs(annotated - (r["start"] * 1e9 + offset)) < 1e6

    def test_every_anchor_gives_one_offset_and_has_its_record(
            self, tmp_path):
        """Two clocks of one host: every ``lzy.clock.<ns>`` annotation in
        the capture's host plane puts ``time.monotonic()`` at the same
        place on the profiler's clock, to within a millisecond, and each
        is listed in ``spans.jsonl`` as a ``lzy.clock`` record."""
        import glob
        import time

        from jax.profiler import ProfileData

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.serving import PagedInferenceEngine

        cfg = llama.LlamaConfig.tiny(vocab_size=64)
        params = unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])
        eng = PagedInferenceEngine(cfg, params, slots=1)
        with profiled(str(tmp_path / "trace")) as logdir:
            eng.start()                  # parked: an idle round a wake-up
            time.sleep(2.2)
            eng.close()
        records = [json.loads(line) for line in
                   open(f"{logdir}/{trace.SPANS_FILE}")][1:]
        listed = {r["attrs"]["monotonic_ns"] for r in records
                  if r["name"] == trace.CLOCK}
        path, = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
        found = {int(e.name[len(trace.CLOCK_ANCHOR):]): float(e.start_ns)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU" for line in plane.lines
                 for e in line.events
                 if e.name.startswith(trace.CLOCK_ANCHOR)}
        assert len(found) >= 3 and set(found) == listed
        offsets = [start - ns for ns, start in found.items()]
        assert max(offsets) - min(offsets) < 1e6

    def test_a_programs_build_is_an_annotation_on_the_anchors_clock(
            self, tmp_path):
        """``program.build`` lies in the capture's host plane where its
        record says, so a device gap during a build has a name."""
        import glob

        from jax.profiler import ProfileData

        x = jnp.ones((4, 4))
        with profiled(str(tmp_path / "trace")) as logdir:
            with trace.building(trace.SITE_DECODE, width=4):
                float(jax.jit(lambda v: (v @ v).sum() * 17)(x))
        records = [json.loads(line) for line in
                   open(f"{logdir}/{trace.SPANS_FILE}")][1:]
        build, = [r for r in records if r["name"] == trace.PROGRAM_BUILD]
        assert build["attrs"]["site"] == trace.SITE_DECODE
        assert build["attrs"]["compile_requests"] == 1
        path, = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
        events = [(float(e.start_ns), float(e.duration_ns), e.name)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU" for line in plane.lines
                  for e in line.events]
        offset, = {s - int(n[len(trace.CLOCK_ANCHOR):]) for s, _, n in events
                   if n.startswith(trace.CLOCK_ANCHOR)}
        (start, duration), = [(s, d) for s, d, n in events
                              if n == trace.PROGRAM_BUILD]
        assert abs(start - (build["start"] * 1e9 + offset)) < 1e6
        assert abs(duration - (build["end"] - build["start"]) * 1e9) < 1e6

    def test_upload_to_storage(self, tmp_path):
        from lzy_tpu.storage.mem import MemStorageClient

        client = MemStorageClient()
        with profiled(str(tmp_path / "t"), upload_prefix="mem://traces/x",
                      storage=client):
            float(jax.jit(lambda x: x * 2)(jnp.ones(8)).sum())
        uploaded = list(client.list("mem://traces/x"))
        assert uploaded, "no artifacts uploaded"
        assert any(u.endswith("/" + trace.SPANS_FILE) for u in uploaded)


@op
def profiled_matmul(n: int) -> float:
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n))
    return float(jax.jit(lambda a: (a @ a).sum())(x))


class TestOpLevelProfiling:
    def test_lzy_profile_env_uploads_trace(self, tmp_path):
        c = InProcessCluster(db_path=str(tmp_path / "m.db"),
                             storage_uri=f"file://{tmp_path}/storage")
        try:
            lzy = c.lzy()
            with lzy.workflow("prof-wf") as wf:
                r = profiled_matmul.with_env_vars({"LZY_PROFILE": "1"})(8)
                assert r == pytest.approx(8 * 8 * 8)
            traces = [u for u in c.storage_client.list(
                f"file://{tmp_path}/storage") if "/traces/" in u]
            assert traces, "op-level profiling produced no stored artifacts"
            assert any(u.endswith(trace.SPANS_FILE) for u in traces)
        finally:
            c.shutdown()

    def test_no_profile_env_no_traces(self, tmp_path):
        c = InProcessCluster(db_path=str(tmp_path / "m.db"),
                             storage_uri=f"file://{tmp_path}/storage")
        try:
            lzy = c.lzy()
            with lzy.workflow("noprof-wf"):
                assert profiled_matmul(4) == pytest.approx(4 * 4 * 4)
            traces = [u for u in c.storage_client.list(
                f"file://{tmp_path}/storage") if "/traces/" in u]
            assert traces == []
        finally:
            c.shutdown()


class TestProfileGate:
    def test_truthiness_parsing(self):
        from lzy_tpu.utils.trace import profile_enabled

        assert profile_enabled({"LZY_PROFILE": "1"})
        assert profile_enabled({"LZY_PROFILE": "true"})
        assert not profile_enabled({"LZY_PROFILE": "0"})
        assert not profile_enabled({"LZY_PROFILE": "false"})
        assert not profile_enabled({})
        assert not profile_enabled(None)
