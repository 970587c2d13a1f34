"""A decode step's share of its HBM roofline, for a model whose expert read
depends on who is resident. Bound: HBM. As ``harness/readers.py``
``decode_roofline`` (the least time the traced decode rounds could take
against the device time of ``module``), but the need is counted from what
the program counted: rows a round and, by the two counts ``counted`` names,
the share of the held experts a round reached, both from the traced rounds'
``engine.decode.emit`` spans (``readers/counted_rows.py``). The model file's
``decode_step_bytes(cfg, param_bytes, resident_tokens, rows, share)`` takes
them; the expectation under uniform routing never stands in (it read 143%
once: PERF.md section 6, PR 29). The context a round's rows hold is the
clients' side (``accounting.resident_tokens``: a mean over the traced span),
scaled from the rows the clients saw resident to the rows a round carried.
None where the traced rounds carry no counts."""

from benchmark.harness import accounting, costs
from benchmark.readers import counted_rows


def read(obs, *, module, counted):
    xs = (obs.get("trace") or {}).get("modules", {}).get(module)
    m, span = obs.get("model") or {}, obs.get("trace_span")
    count = getattr(m.get("module"), "decode_step_bytes", None)
    if not xs or not span or not obs.get("rows") or count is None:
        return None
    rows = counted_rows.rows_a_round(obs)
    share = counted_rows.counted_share(obs, *counted)
    seen = accounting.resident_rows(obs["rows"], *span)
    if rows is None or share is None or not seen:
        return None
    tokens = accounting.resident_tokens(obs["rows"], *span) * rows / seen
    need = count(m["cfg"], m["param_bytes"], tokens, rows, share)
    least = need / costs.peaks(obs["device_kind"])["bytes_per_s"]
    return 100.0 * least * len(xs) / sum(xs)
