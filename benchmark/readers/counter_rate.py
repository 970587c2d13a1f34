"""A counter of the program as a rate: the window's difference of the named
counters (``obs["counters"]``: the registry's exposition names and the
numeric values of ``fleet.aggregate()``), added up, over the window's
seconds. None where the program has none of them."""


def read(obs, *, counters):
    c = obs.get("counters") or {}
    seconds = obs.get("t_close", 0) - obs.get("t_open", 0)
    if seconds <= 0 or not any(k in c for k in counters):
        return None
    return sum(c.get(k, 0.0) for k in counters) / seconds
