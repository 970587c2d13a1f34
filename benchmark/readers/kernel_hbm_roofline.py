"""A kernel's share of its HBM roofline inside a decode step. Bound: HBM.
The least time the traced steps' calls of the kernel could take (the bytes
the configuration's model file says one decode round makes the kernel move,
``bytes_fn(cfg, rows[, share])``, times the traced executions of ``module``,
over the chip's bytes/s) against the device time the kernel's operations
took. Rows a round and, where ``counted`` names two of the program's counts,
their ratio (the share of the held experts a round reached) are what the
program counted on each traced round's ``engine.decode.emit`` span
(``readers/counted_rows.py``): the need is what the kernel had to move, so
the share cannot pass 100% because the traffic routed unevenly. None where
the traced rounds carry no counts."""

from benchmark.harness import costs
from benchmark.readers import counted_rows
from benchmark.readers.op_share import kernel_seconds


def read(obs, *, match, module, bytes_fn, counted=None):
    m = obs.get("model") or {}
    steps = (obs.get("trace") or {}).get("modules", {}).get(module)
    count = getattr(m.get("module"), bytes_fn, None)
    mine = kernel_seconds(obs, match=match, module=module)
    rows = counted_rows.rows_a_round(obs)
    if not steps or count is None or mine is None or not mine[0] \
            or rows is None:
        return None
    extra = ()
    if counted:
        share = counted_rows.counted_share(obs, *counted)
        if share is None:
            return None
        extra = (share,)
    need = count(m["cfg"], rows, *extra)
    least = need * len(steps) / costs.peaks(obs["device_kind"])["bytes_per_s"]
    return 100.0 * least / mine[0]
