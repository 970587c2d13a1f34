"""A prefill kernel's share of its HBM roofline. Bound: HBM (the model
file's ``bytes_fn`` says why, and what it leaves uncharged). The bytes the
traced span's prefill programs made the kernel move, over the chip's
bytes/s, against the device time of the kernel's operations inside
``module``. How many real positions those programs carried is what the
program's ``engine.prefill`` spans say (``tokens``: how many the round
advanced; ``chunks``: the programs it took, one where the span does not
say), over the spans that ended inside the traced span; the model file's
``bytes_fn(cfg, tokens, programs)`` turns them into bytes. As
``readers/chunk_read_roofline.py``, which holds a compute-bound kernel to
the chip's peak the same way. None where no such span carries ``tokens`` or
the trace holds no such kernel."""

from benchmark.harness import costs
from benchmark.readers.op_share import kernel_seconds

_PREFILL = "engine.prefill"


def read(obs, *, match, module, bytes_fn):
    m, span = obs.get("model") or {}, obs.get("trace_span")
    count = getattr(m.get("module"), bytes_fn, None)
    mine = kernel_seconds(obs, match=match, module=module)
    if not span or count is None or mine is None or not mine[0]:
        return None
    rounds = [s["attrs"] for s in obs.get("spans") or []
              if s["name"] == _PREFILL and span[0] <= s["end"] <= span[1]
              and (s.get("attrs") or {}).get("tokens")]
    if not rounds:
        return None
    need = sum(count(m["cfg"], r["tokens"], r.get("chunks") or 1)
               for r in rounds)
    least = need / costs.peaks(obs["device_kind"])["bytes_per_s"]
    return 100.0 * least / mine[0]
