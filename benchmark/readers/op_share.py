"""Device time of the operations of one jitted program whose name matches,
over the program's own device time, in the traced span: what share of a
step a named kernel takes. The trace's reduction (``harness/trace.py``
``reduce``) files every operation of device 0 under
``<module>:<operation label>``, a Pallas kernel under its ``name``."""

import re


def kernel_seconds(obs, *, match, module):
    """``(seconds, calls)`` of the operations of ``module`` whose label
    matches ``match``; None where the trace holds none."""
    ops = (obs.get("trace") or {}).get("ops") or {}
    found = [v for k, v in ops.items()
             if k.startswith(module + ":")
             and re.search(match, k[len(module) + 1:])]
    if not found:
        return None
    return sum(v[0] for v in found), sum(v[1] for v in found)


def read(obs, *, match, module, scale=100.0):
    whole = (obs.get("trace") or {}).get("modules", {}).get(module)
    mine = kernel_seconds(obs, match=match, module=module)
    if not whole or mine is None:
        return None
    return scale * mine[0] / sum(whole)
