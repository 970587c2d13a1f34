"""A prefill chunk's attention read as a share of its compute roofline.
Bound: compute (the model file's ``flops_fn`` says why). The arithmetic the
traced span's prefill programs had to do in their attention reads, over the
chip's peak, against the device time of the kernel's operations inside
``module``. Which positions those programs carried is what the program's
``engine.prefill`` spans say (``start``: the prompt position of the round's
first token; ``tokens``: how many it advanced), over the spans that ended
inside the traced span; the model file's ``flops_fn(cfg, start, tokens)``
turns them into operations. None where no such span carries a ``start`` (a
program that does not record it) or the trace holds no such kernel."""

from benchmark.harness import costs
from benchmark.readers.op_share import kernel_seconds

_PREFILL = "engine.prefill"


def read(obs, *, match, module, flops_fn):
    m, span = obs.get("model") or {}, obs.get("trace_span")
    count = getattr(m.get("module"), flops_fn, None)
    mine = kernel_seconds(obs, match=match, module=module)
    if not span or count is None or mine is None or not mine[0]:
        return None
    rounds = [s["attrs"] for s in obs.get("spans") or []
              if s["name"] == _PREFILL and span[0] <= s["end"] <= span[1]
              and "start" in (s.get("attrs") or {})
              and s["attrs"].get("tokens")]
    if not rounds:
        return None
    flops = sum(count(m["cfg"], r["start"], r["tokens"]) for r in rounds)
    least = flops / costs.peaks(obs["device_kind"])["flops_per_s"]
    return 100.0 * least / mine[0]
