"""An attention read's share of its HBM roofline inside a decode step, for a
model whose layers read different numbers of keys (some through a window).
Bound: HBM. As ``readers/kernel_hbm_roofline.py`` (the least time the traced
steps' calls of the kernel could take against the device time its operations
took), but the need is sized by a *sum* of the program's counts: ``keys``
names the counters whose counts, added up, are the cached keys a round's rows
read over all layers, as each traced round's ``engine.decode.emit`` span
carries them (``readers/counted_rows.py``). The model file's
``bytes_fn(cfg, keys a round)`` turns them into bytes. None where the traced
rounds carry no counts (a program without the counters)."""

from benchmark.harness import costs
from benchmark.readers import counted_rows
from benchmark.readers.op_share import kernel_seconds


def read(obs, *, match, module, bytes_fn, keys):
    m = obs.get("model") or {}
    steps = (obs.get("trace") or {}).get("modules", {}).get(module)
    count = getattr(m.get("module"), bytes_fn, None)
    mine = kernel_seconds(obs, match=match, module=module)
    rounds = counted_rows.traced_rounds(obs)
    if not steps or count is None or mine is None or not mine[0] \
            or not rounds:
        return None
    read_keys = sum(r["model_stats"].get(k, 0) for r in rounds for k in keys)
    if not read_keys:
        return None
    need = count(m["cfg"], read_keys / len(rounds))
    least = need * len(steps) / costs.peaks(obs["device_kind"])["bytes_per_s"]
    return 100.0 * least / mine[0]
