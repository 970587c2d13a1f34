"""Serving-plane resolution for the ``llm`` op surface.

An ``llm.generate`` op body runs wherever the runtime puts it (the
user's process under ``LocalRuntime``, a worker thread under the
in-process cluster, a worker process on a real deployment) and needs a
serving plane to dispatch to. A :class:`LlmBackend` is that handle:
anything with the ``InferGenerate`` method surface — a
``GatewayService``, a ``DisaggGatewayService``, a single-engine
``InferenceService``, or an ``RpcInferenceClient`` dialing a remote
plane — wrapped with the two things the op layer additionally needs:

- a **model digest** (part of the op cache key: a cached generation must
  be invalidated when the served model changes);
- the **credential** for the plane (the backend holds the bearer token;
  it never travels through the workflow snapshot as an op argument).

Resolution order for the op body:

1. the process-global backend set by :func:`configure` (tests, local
   runs, in-process clusters);
2. ``LZY_LLM_ENDPOINT`` (+ optional ``LZY_LLM_TOKEN``): a remote worker
   dials the serving plane over RPC.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Optional

from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)


class LlmBackendError(RuntimeError):
    """No serving plane is reachable from this process."""


def model_digest_for(model_name: str, cfg: Any = None,
                     checkpoint: Optional[str] = None,
                     seed: Optional[int] = None) -> str:
    """Deterministic digest of what the plane serves: model name +
    config fields + weight provenance (checkpoint path, init seed). The
    weights themselves are not hashed — a terabyte-scale params tree
    cannot be fingerprinted per request — so two planes that lie about
    the same checkpoint path collide; the builders
    (``service/inference.py``) thread honest values here."""
    doc = {"model": model_name, "checkpoint": checkpoint, "seed": seed}
    if cfg is not None:
        fields = getattr(cfg, "__dict__", None) or {}
        doc["cfg"] = {k: repr(v) for k, v in sorted(fields.items())
                      if not k.startswith("_")}
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode("utf-8")).hexdigest()[:16]


def _is_signature_mismatch(e: TypeError) -> bool:
    """CPython's call-machinery wording for a kwarg the callee does not
    accept — the one TypeError the degradation ladder may swallow."""
    msg = str(e)
    return ("unexpected keyword argument" in msg
            or "takes no keyword arguments" in msg)


class ServiceBackend:
    """Wrap anything speaking the ``InferGenerate`` surface.

    ``token`` is the bearer credential presented per call (None on an
    IAM-less plane — or for an ``RpcInferenceClient`` that already
    carries its own). ``digest`` overrides the model digest; otherwise
    the service's ``model_digest`` attribute (set by the serve builders)
    is used, falling back to a config-derived digest off a live engine.
    """

    def __init__(self, service: Any, *, token: Optional[str] = None,
                 digest: Optional[str] = None):
        self.service = service
        self.token = token
        self._digest = digest

    @property
    def model_name(self) -> str:
        return getattr(self.service, "model_name", "custom")

    def model_digest(self) -> str:
        if self._digest is None:
            self._digest = getattr(self.service, "model_digest", None) \
                or self._derive_digest()
        return self._digest

    def _derive_digest(self) -> str:
        cfg = None
        engine = getattr(self.service, "engine", None)
        if engine is not None:
            cfg = getattr(engine, "cfg", None)
        else:
            fleet = getattr(self.service, "fleet", None)
            if fleet is not None:
                for replica in fleet.replicas():
                    cfg = getattr(replica.engine, "cfg", None)
                    if cfg is not None:
                        break
        return model_digest_for(self.model_name, cfg)

    def generate(self, prompt, **kwargs) -> dict:
        if kwargs.get("token") is None:
            kwargs["token"] = self.token
        # None-valued extension kwargs are dropped UP FRONT: a surface
        # that takes session but not token (RpcInferenceClient — it
        # carries its own credential; since the streaming PR it DOES
        # take stream, long-polling InferStream frames into the channel
        # incrementally) must still receive the session hint, not be
        # forced onto the degraded path by a None it cannot accept
        for opt in ("token", "session", "stream"):
            if kwargs.get(opt) is None:
                kwargs.pop(opt, None)
        stream = kwargs.get("stream")
        # older surfaces degrade one extension at a time: stream first
        # (the terminal flush below makes that correct, not lossy), then
        # session (a routing HINT — a stale one costs a prefill, never a
        # wrong token). A non-None token is never dropped: silently
        # calling an IAM plane unauthenticated would be lossy.
        attempts = [kwargs]
        for drop in (("stream",), ("stream", "session")):
            trimmed = {k: v for k, v in kwargs.items() if k not in drop}
            if trimmed != attempts[-1]:
                attempts.append(trimmed)
        reply = kw = None
        for i, kw in enumerate(attempts):
            try:
                reply = self.service.generate(prompt, **kw)
                break
            except TypeError as e:
                # only an actual SIGNATURE mismatch degrades — a
                # TypeError raised from inside the surface (bad operand
                # types deep in the service) must surface, not trigger a
                # silent re-dispatch of work the plane may have done
                if i == len(attempts) - 1 or \
                        not _is_signature_mismatch(e):
                    raise
        if stream is not None and "stream" not in kw:
            try:
                stream.publish(0, reply.get("tokens", []))
                stream.close(reply.get("status", "ok"))
            except Exception:  # noqa: BLE001 — reply owns the data
                pass
        return reply


class EngineBackend:
    """Wrap a raw in-process engine (``PagedInferenceEngine`` or subclass)
    for ``LocalRuntime`` dev loops: no gateway, no routing metadata —
    ``submit`` + wait shaped into the reply dict the op layer reads."""

    def __init__(self, engine: Any, *, model_name: str = "custom",
                 digest: Optional[str] = None):
        self.engine = engine
        self.model_name = model_name
        self.token = None
        self._digest = digest

    def model_digest(self) -> str:
        if self._digest is None:
            self._digest = model_digest_for(
                self.model_name, getattr(self.engine, "cfg", None))
        return self._digest

    def generate(self, prompt, *, max_new_tokens: int = 64,
                 timeout_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 greedy: Optional[bool] = None,
                 tenant: Optional[str] = None,
                 priority: Optional[int] = None,
                 session: Optional[str] = None,
                 stream=None, token: Optional[str] = None) -> dict:
        req = self.engine.submit(
            prompt, max_new_tokens=int(max_new_tokens),
            deadline_s=deadline_s, greedy=greedy,
            tenant=tenant or "default", priority=priority)
        if stream is not None:
            from lzy_tpu.channels.token_stream import attach_request

            attach_request(stream, req, 0)
        try:
            if not req.wait(timeout=timeout_s or 120.0):
                req.cancel()
                raise TimeoutError(
                    f"request {req.id} not finished within "
                    f"{timeout_s or 120.0}s")
            if req.error and req.status != "cancelled":
                raise RuntimeError(
                    f"request {req.id} failed: {req.error}")
        except BaseException as e:
            from lzy_tpu.channels.token_stream import fail_if_touched

            fail_if_touched(stream, e)
            raise
        if stream is not None:
            stream.close(req.status or "ok")
        ttft_ms = None
        if req.first_token_at is not None:
            ttft_ms = round(1000 * (req.first_token_at
                                    - req.submitted_at), 3)
        from lzy_tpu.serving.scheduler import plane_capacity

        return {"request_id": req.id, "tokens": list(req.tokens),
                "status": req.status or "ok", "ttft_ms": ttft_ms,
                "model": self.model_name,
                **plane_capacity(self.engine.stats().slots)}


_lock = threading.Lock()
_configured: Optional[Any] = None


def configure(backend: Any, *, token: Optional[str] = None) -> Any:
    """Set the process-global serving backend the ``llm`` op surface
    dispatches to. Accepts a ready :class:`ServiceBackend` /
    :class:`EngineBackend`, or any ``InferGenerate``-shaped service
    (wrapped in a :class:`ServiceBackend`). Returns the installed
    backend. ``configure(None)`` clears."""
    global _configured
    if backend is not None and \
            not callable(getattr(backend, "model_digest", None)):
        # a service object (its model_digest, if any, is a plain string
        # the builders attached) — wrap it in the backend adapter
        backend = ServiceBackend(backend, token=token)
    with _lock:
        _configured = backend
    # a new plane must not inherit the old plane's workflow scheduler
    # state (in-flight dedup table, fusion leases, speculation futures)
    from lzy_tpu.llm import sched

    sched.reset()
    return backend


def resolve_backend() -> Any:
    """The backend an op body should dispatch to (resolution order in
    the module docstring). Raises :class:`LlmBackendError` when nothing
    is reachable — the op fails with a clear cause instead of a hang."""
    with _lock:
        if _configured is not None:
            return _configured
    endpoint = os.environ.get("LZY_LLM_ENDPOINT")
    if endpoint:
        from lzy_tpu.rpc.control import RpcInferenceClient

        client = RpcInferenceClient(
            endpoint, token=os.environ.get("LZY_LLM_TOKEN"))
        _LOG.info("llm backend: dialing %s", endpoint)
        return ServiceBackend(
            client, digest=os.environ.get("LZY_LLM_MODEL_DIGEST"))
    raise LlmBackendError(
        "no llm serving backend: call lzy_tpu.llm.configure(<service>) "
        "in this process, or set LZY_LLM_ENDPOINT for a remote plane")
