"""One statistic over the seconds of the program's spans of one name, or of
a list of names taken together: ``mean``, ``p50`` or ``max``. A span counts
where its end lies inside ``clip``: ``"window"`` (``t_open`` to ``t_close``;
the recorder is on from the window's start) or ``"trace"`` (the traced
span). It counts with its whole length, so a request's wait that began
before the window and a stall that straddles its start are not cut short.
None where no such span ended there."""

from benchmark.harness.common import percentile


def seconds(obs, names, clip) -> list:
    inside = (obs["t_open"], obs["t_close"]) if clip == "window" \
        else obs.get("trace_span")
    if not inside:
        return []
    names = {names} if isinstance(names, str) else set(names)
    return [s["end"] - s["start"] for s in obs.get("spans") or []
            if s["name"] in names and inside[0] <= s["end"] <= inside[1]]


def read(obs, *, name, stat, clip="window"):
    xs = seconds(obs, name, clip)
    if not xs:
        return None
    if stat == "mean":
        return sum(xs) / len(xs)
    return max(xs) if stat == "max" else percentile(xs, float(stat[1:]))
